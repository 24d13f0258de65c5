"""Operations and bytes that the work needs, counted from shapes and lengths.

Every count is what the operation requires at the call's shapes and the
rows' true lengths, whatever implements it: not the padded blocks a kernel
happens to fetch, so a kernel rewrite leaves the count valid.  FLOPs count
multiply-adds as two.  Bytes are those each operand must cross HBM once.

The kernel functions take one call's arguments and return ``(flops,
bytes)``.  What a model step gives each kernel, and the FLOPs of its trunk,
depend on the family and live in ``bench/families/<family>.py``; the
unembedding of one token is the same for every family and stays here.
"""
from __future__ import annotations

BF16, F32 = 2, 4


# -- kernels ------------------------------------------------------------------


def flash_attention(batch: int, seq: int, heads: int, kv_heads: int,
                    head_dim: int, itemsize: int = BF16) -> tuple:
    """Causal self-attention over ``seq`` tokens: QK^T and PV over the
    seq * (seq + 1) / 2 query-key pairs of each (row, head)."""
    pairs = seq * (seq + 1) // 2
    flops = 4 * head_dim * batch * heads * pairs
    nbytes = itemsize * batch * seq * head_dim * (2 * heads + 2 * kv_heads)
    return flops, nbytes


def bank_matmul(members: int, rows: int, d_in: int, d_out: int,
                x_itemsize: int = BF16, w_itemsize: int = BF16,
                out_itemsize: int = F32) -> tuple:
    """out[n] = x[n] @ w[n] for every bank member n (banked features)."""
    flops = 2 * members * rows * d_in * d_out
    nbytes = (members * rows * d_in * x_itemsize
              + members * d_in * d_out * w_itemsize
              + members * rows * d_out * out_itemsize)
    return flops, nbytes


def decode_attention(lengths, heads: int, kv_heads: int, head_dim: int,
                     itemsize: int = BF16) -> tuple:
    """One new token per row against its cache: for a row that attends
    ``length`` positions, QK^T and PV over ``length`` keys per query head,
    K and V of ``length`` positions read once, its query read and its
    output written.  ``lengths`` holds the real rows' attended lengths; a
    padded row or the cache's room beyond a row's length is no work."""
    total = sum(int(n) for n in lengths)
    flops = 4 * head_dim * heads * total
    nbytes = itemsize * head_dim * (2 * kv_heads * total
                                    + 2 * heads * len(lengths))
    return flops, nbytes


def page_gather(pages: int, page_bytes: int) -> tuple:
    """Assembling ``pages`` pool pages of ``page_bytes`` into rows: each
    page read once and written once; no arithmetic."""
    return 0, 2 * pages * page_bytes


def add(work: dict, kernel: str, fb: tuple, times: int = 1) -> None:
    """Accumulate ``times`` calls' ``(flops, bytes)`` under ``kernel`` in
    ``work`` (kernel -> [flops, bytes])."""
    acc = work.setdefault(kernel, [0, 0])
    acc[0] += fb[0] * times
    acc[1] += fb[1] * times


# -- model steps ----------------------------------------------------------------


def head_token_flops(m) -> int:
    """One head's unembedding of one token."""
    return 2 * m.d_model * m.vocab
