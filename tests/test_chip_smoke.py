"""``chip_smoke.py`` off the chip: it refuses to run without a TPU in
kernel mode, and its phases pass end to end at a tiny width with the Pallas
kernel bodies interpreted on the CPU."""
import jax.numpy as jnp
import pytest

import chip_smoke
from repro.kernels import ops
from repro.models.registry import get_adapter
from repro.models.transformer import DenseLMConfig

TINY = DenseLMConfig(
    name="tiny-stablelm", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    head_dim=16, d_ff=128, vocab_size=512, rotary_pct=0.25, norm="layernorm",
    dtype=jnp.bfloat16, scan_layers=False)
TINY_DECODE = dict(prompt_len=6, new_tokens=4, page_size=4, max_len=12,
                   per_variant=2)


@pytest.mark.parametrize("mode", [None, "ref", "interpret"])
def test_refuses_without_tpu_kernels(mode, monkeypatch, capsys):
    if mode is None:
        monkeypatch.delenv("REPRO_KERNEL_MODE", raising=False)
    else:
        monkeypatch.setenv("REPRO_KERNEL_MODE", mode)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_tolerance_rejects_coarse_logits():
    """The bounds pass bf16-grade error and fail a coarser one."""
    import numpy as np

    rng = np.random.default_rng(0)
    ref = rng.standard_normal((4, 512)).astype(np.float32)
    bf16 = np.asarray(jnp.asarray(ref, jnp.bfloat16), np.float32)
    assert chip_smoke.within_tolerance(chip_smoke.compare(bf16, ref))
    coarse = ref + 0.1 * rng.standard_normal(ref.shape).astype(np.float32)
    assert not chip_smoke.within_tolerance(chip_smoke.compare(coarse, ref))


def test_main_path_tiny_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    ops.reset_dispatch_counts()
    out, fails = chip_smoke.run_main_path(
        get_adapter("dense"), TINY, capacity_bytes=10**9, prompt_len=16,
        decode=TINY_DECODE)
    assert fails == []
    assert out["merged_bytes"] < out["unmerged_bytes"]
    assert out["serve"]["completed"] == 2 * chip_smoke.SCORE_PER_VARIANT
    assert out["decode"]["tokens_decoded"] == 4 * TINY_DECODE["new_tokens"]
    counts = ops.dispatch_counts()
    assert all(counts.get(k) for k in chip_smoke.KERNELS), counts
    progs = chip_smoke.compiled_programs(
        get_adapter("dense"), TINY, out["engine"], out["decode"]["num_pages"],
        prompt_len=16, decode=TINY_DECODE)
    assert set(progs) == {"trunk", "bank_head", "decode_step"}
    assert all(p.memory_analysis() is not None for p in progs.values())


def test_sharded_bank_phase_tiny_interpret(monkeypatch):
    """The ``--chips 4`` phase over whatever devices the host has (one on
    the plain CPU lane): one-device and mesh-placed lanes agree."""
    import dataclasses

    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    monkeypatch.setattr(chip_smoke, "full_config",
                        lambda n_layers: dataclasses.replace(TINY, n_layers=n_layers))
    monkeypatch.setattr(chip_smoke, "PROMPT_LEN", 16)
    assert chip_smoke.sharded_bank(get_adapter("dense"), 10**9, seed=0) == []


@pytest.mark.parametrize("env_dir", [None, "/var/cache/jax-shared"])
def test_compile_cache_dir(env_dir, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set and nothing overrides it;
    otherwise the cache sits at the checkout's fixed ``.jax_cache/``."""
    import jax

    from repro.utils import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert got == str(compile_cache.CHECKOUT_CACHE)
            assert got.endswith("/.jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
