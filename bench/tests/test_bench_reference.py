"""The plain reference against the program's own forward pass (``ref``
kernel mode, float32, small widths on the CPU), and the float8 control
against the reference."""
import jax
import numpy as np
import pytest

from bench import model, weights
from bench.references import dense
from bench.tests import tiny


def zoo(**over):
    cfg = tiny.cell(config_overrides=over).config
    m = model.from_config(cfg)
    return (m, model.family(m.family).program_config(m, cfg["program"]),
            weights.zoo(m, 5))


def test_reference_matches_program_forward(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    from repro.models import transformer

    m, pcfg, (trunk, heads) = zoo(num_hidden_layers=3)
    toks = np.random.default_rng(0).integers(0, m.vocab, (2, 40), dtype=np.int32)
    for v in (0, m.variants - 1):
        params = weights.variant_params(trunk, heads[v])
        got = np.asarray(transformer.forward(pcfg, params, jax.numpy.asarray(toks)))
        want = np.asarray(dense.logits(m, trunk, heads[v], toks))
        scale = np.sqrt(np.mean(want ** 2))
        assert np.max(np.abs(got - want)) / scale < 1e-4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fp8_control_departs_from_reference(dtype):
    m, _, (trunk, heads) = zoo(torch_dtype=dtype)
    toks = np.random.default_rng(1).integers(0, m.vocab, (1, 24), dtype=np.int32)
    f32 = np.asarray(dense.logits(m, trunk, heads[0], toks))
    fp8 = np.asarray(dense.logits(m, trunk, heads[0], toks, "fp8"))
    err = np.max(np.abs(fp8 - f32)) / np.sqrt(np.mean(f32 ** 2))
    assert 0.05 < err < 2.0


def test_weights_follow_the_program_layout_and_the_seed():
    from repro.models.registry import get_adapter

    m, pcfg, (trunk, heads) = zoo()
    adapter = get_adapter(tiny.load("configs", tiny.CONFIG)["program"]["adapter"])
    weights.check_layout(weights.variant_params(trunk, heads[1]),
                         adapter.eval_params(pcfg))
    again, _ = weights.zoo(m, 5)
    other, _ = weights.zoo(m, 2 ** 33 + 5)
    a, b, c = (np.asarray(t["embed"]["table"]) for t in (trunk, again, other))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert not np.array_equal(np.asarray(heads[0]["lm_head"]["w"]),
                              np.asarray(heads[1]["lm_head"]["w"]))
    with pytest.raises(ValueError):
        weights.check_layout({"x": np.zeros(2)}, {"x": np.zeros(3)})
