"""The sizes of one zoo, read from its configuration file.

A configuration file under ``bench/configs/`` keeps the published model's
own key names (``hidden_size``, ``num_hidden_layers``, ...) so that it can be
read against its source, and names its ``family``.  Everything that depends
on the family lives in ``bench/families/<family>.py`` (the sizes record it
builds from the file, the weight generator, the program's config object, the
FLOPs of a token and the work a step gives each kernel) and its plain
reference in ``bench/references/<family>.py``.  Both are found by that name
alone, so a family enters the benchmark by adding files.
"""
from __future__ import annotations

import dataclasses
import importlib

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes every family has.  A family's record subclasses it with
    its own widths; the record is frozen and hashable, so it can be a
    static argument of a jitted call."""
    family: str  # module name under bench/families/ and bench/references/
    n_layers: int
    d_model: int
    vocab: int
    variants: int
    dtype_name: str = "bfloat16"

    @property
    def dtype(self):
        return jnp.dtype(self.dtype_name)


def common(cfg: dict) -> dict:
    """The :class:`Model` fields of a configuration file, under their own
    names, for a family's ``from_config`` to extend."""
    return dict(family=cfg["family"], n_layers=cfg["num_hidden_layers"],
                d_model=cfg["hidden_size"], vocab=cfg["vocab_size"],
                variants=cfg["zoo"]["variants"], dtype_name=cfg["torch_dtype"])


def family(name: str):
    """The module ``bench/families/<name>.py``."""
    return importlib.import_module(f"bench.families.{name}")


def from_config(cfg: dict) -> Model:
    """The sizes record a configuration file describes, built by its
    family."""
    return family(cfg["family"]).from_config(cfg)
