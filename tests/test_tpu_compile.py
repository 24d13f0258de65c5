"""Compile-only checks: every ``OP_TABLE`` kernel, at the widths the serving
path runs it, compiles for a described TPU v5e.

Interpret mode never applies the TPU lowering's rules (block shapes whose
last two dims are (8, 128)-aligned or whole, VMEM limits), so the numeric
kernel tests pass for layouts the chip refuses.  These tests lower the
``kernel``-mode entry points against a ``v5e:2x2`` topology described on
this host — nothing runs, so they cost no chip time — and check that the
compiled program holds the Pallas custom call.  Widths: StableLM-2-1.6B for
the dense serving kernels, falcon-mamba-7b for ``mamba_scan``,
recurrentgemma-9b (d_rnn=4096) for ``rg_lru_scan``; batch > 1 throughout.

The topology is described inside a fixture (only one process at a time may
hold the TPU library), and the persistent compilation cache is off around
the compiles: a program compiled for a described chip cannot be read back.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# StableLM-2-1.6B: 32 heads of 64, d=2048, vocab 100352; four rows per
# batch, 128-token prompts, a 64-slot paged cache of 16-token pages
B, S, H, D, DM, V = 4, 128, 32, 64, 2048, 100352
PAGE_ELEMS = 16 * H * D  # one page of one layer's K (or V), flattened

CASES = {
    "flash_attention/prompt": (
        "flash_attention", [((B, S, H, D), BF16)] * 3, dict(causal=True)),
    "flash_attention/padded_seq": (  # 320 tokens: padded to 384 internally
        "flash_attention", [((B, 320, H, D), BF16)] * 3, dict(causal=True)),
    "decode_attention/paged_view": (
        "decode_attention",
        [((B, H, D), BF16), ((B, 64, H, D), BF16), ((B, 64, H, D), BF16),
         ((B,), I32)], {}),
    "decode_attention/long_cache": (  # 4096 positions, 256-wide blocks
        "decode_attention",
        [((B, H, D), BF16), ((B, 4096, H, D), BF16), ((B, 4096, H, D), BF16),
         ((B,), I32)], {}),
    "page_gather/kv_pages": (
        "page_gather", [((16, PAGE_ELEMS), BF16), ((B * 4,), I32)], {}),
    "bank_matmul/prompt_head": (
        "bank_matmul", [((2, B * S, DM), BF16), ((2, DM, V), BF16)], {}),
    "bank_matmul/prompt_head_bias": (
        "bank_matmul",
        [((2, B * S, DM), BF16), ((2, DM, V), BF16), ((2, V), BF16)], {}),
    "bank_matmul/decode_head": (  # one token per row, broadcast features
        "bank_matmul", [((B, DM), BF16), ((2, DM, V), BF16)], {}),
    "bank_matmul/ragged_rows_bias": (  # M=520: one whole-M block, no pad
        "bank_matmul",
        [((2, 520, DM), BF16), ((2, DM, V), BF16), ((2, V), BF16)], {}),
    "bank_matmul/zoo4_head": (  # four variants' heads: blocks above 16 MiB
        "bank_matmul", [((4, B * S, DM), BF16), ((4, DM, V), BF16)], {}),
    "mamba_scan/falcon_mamba": (  # d_inner 8192, d_state 16, chunk 256
        "mamba_scan",
        [((B, 256, 8192), F32), ((B, 256, 8192), F32), ((B, 256, 16), F32),
         ((B, 256, 16), F32), ((8192, 16), F32), ((B, 8192, 16), F32)],
        dict(chunk=256)),
    "rg_lru_scan/recurrentgemma": (  # d_rnn 4096, chunk 256
        "rg_lru_scan",
        [((B, 256, 4096), F32), ((B, 256, 4096), F32), ((B, 4096), F32)],
        dict(chunk=256)),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def test_every_op_has_a_compile_case():
    assert {op for op, _, _ in CASES.values()} == set(ops.OP_TABLE)


def compiled_text(case: str, sharding) -> str:
    op, shapes, kw = CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    fn = functools.partial(ops.OP_TABLE[op].dispatch, mode="kernel", **kw)
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    assert "tpu_custom_call" in compiled_text(case, one_chip)


@pytest.mark.parametrize("op", sorted(ops.OP_TABLE))
def test_kernel_call_is_named_for_its_op(op, one_chip, no_compile_cache):
    """The device trace names a kernel by its HLO instruction, and the
    roofline readers match that name from its start (``^bank_matmul``,
    ``^flash_attention``): each Pallas call carries its op's name."""
    case = min(c for c, (o, _, _) in CASES.items() if o == op)
    calls = re.findall(r"^\s*(?:ROOT )?%?([\w.-]+) = .*"
                       r'custom_call_target="tpu_custom_call"',
                       compiled_text(case, one_chip), re.M)
    assert any(name.startswith(op) for name in calls), calls
