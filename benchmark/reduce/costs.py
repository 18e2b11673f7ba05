"""Operations and bytes a kernel's call needs, from its shapes alone.

The least work the algorithm requires, not what an implementation happens to
do: a causal prefill attends to half the square. A later PR adds a kernel's
cost as a module of its own beside this one (`kernels/<kernel>.json` names the
function as `<module>.<function>`).
"""

from __future__ import annotations


def prefill_attention(rows: int, width: int, n_heads: int, n_kv_heads: int,
                      head_dim: int, bytes_per_elem: int = 2) -> dict:
    """Causal self-attention over `rows` sequences of `width` tokens."""
    pairs = rows * n_heads * width * (width + 1) // 2  # causal (query, key) pairs
    ops = 4 * pairs * head_dim  # q.k and p.v, a multiply and an add each
    q_and_out = 2 * rows * width * n_heads * head_dim
    k_and_v = 2 * rows * width * n_kv_heads * head_dim
    return {"ops": ops, "bytes": (q_and_out + k_and_v) * bytes_per_elem}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound it is."""
    compute = cost["ops"] / peaks["bf16_flops_per_s"]
    memory = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
