"""What the gated delta rule has to move and compute, from the recurrence
alone: per token, head and layer the state S [dk, dv] is decayed, read once
by k (S^T k), written once (the rank-one update) and read once by q (S^T q),
three dk x dv products of a multiply and an add each. That is the least any
implementation does; the chunked (WY) form's extra products inside a chunk
are its own cost and are not counted, so no implementation reads over 100%.

Decode is bound by memory: a live row's state goes in once and out once a
layer and step. The chunked prefill has no kernel and no roofline metric
(ISSUE 32, item 3: the XLA form shipped), so it has no cost here."""

from __future__ import annotations

STATE_BYTES = 4  # float32, as the published kernels keep it


def _per_token_io(n_heads: int, key_head_dim: int, value_dim: int, bytes_per_elem: int) -> int:
    # q and k in, v in, the decay and beta in (a number a head), o out
    return (2 * n_heads * key_head_dim + 2 * value_dim + 2 * n_heads) * bytes_per_elem


def gated_delta_update(state_rows: int, steps: int, layers: float, rows: int,
                       key_head_dim: int, value_dim: int, n_heads: int,
                       bytes_per_elem: int = 4) -> dict:
    """A decode chunk: `state_rows` (row, step) pairs updated a layer, of the
    `rows` x `steps` the kernel was called on (an idle row costs its q, k, v
    and output, not its state)."""
    state = state_rows * 2 * key_head_dim * value_dim * STATE_BYTES
    io = steps * rows * _per_token_io(n_heads, key_head_dim, value_dim, bytes_per_elem)
    ops = state_rows * 3 * 2 * key_head_dim * value_dim
    return {"ops": ops * layers, "bytes": (state + io) * layers}

