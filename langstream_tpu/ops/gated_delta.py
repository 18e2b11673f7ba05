"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464), the mixer of a
``linear_attention`` layer: per head and sequence a float32 state
S [dk, dv] with

    S_t = a_t S_(t-1) + b_t k_t (v_t - a_t S_(t-1)^T k_t)^T,   o_t = S_t^T q_t

where a_t = exp(g_t) in (0, 1] is the decay and b_t the write strength (in
(0, 2) with ``linear_allow_neg_eigval``). q and k arrive L2-normalised, q
scaled by dk^-1/2.

The state's layout on the device is ``[dk, H * dv]``: the heads folded
into the lane axis, so that 96 x (30 x 192 = 45 x 128) wastes nothing
where ``[H, 96, 192]`` would pad every head's 192 lanes to 256. The whole
model's state is ``[L_linear, rows, dk, H * dv]``; a row belongs to a
serving slot (serving/pagepool.py).

- ``gated_delta_chunk_prefill``: many tokens a row, in chunks of 64 in the
  paper's WY form: inside a chunk the rule is solved as one triangular
  system, between chunks the state is carried by a scan. Matrix products
  in XLA under the scope of that name, no Pallas kernel. It takes an
  initial state, returns the final one, and a position with g = 0 and
  b = 0 (padding) leaves the state as it was.
- ``gated_delta_update``: one token a row (decode), a Pallas kernel that
  reads and writes a LIVE row's state once, in place in the whole model's
  state (operand aliased to the result, the layer an index, as the paged
  attention kernels take the page pool); an idle row moves no byte of
  state. ``gated_delta_update_jnp`` is the same mathematics for the CPU.
- ``gated_delta_recurrent``: the rule token by token (`lax.scan`), what
  the two above are tested against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from langstream_tpu.compile_account import note_kernel
from langstream_tpu.models.configs import ModelConfig

CHUNK = 64
_NORM_EPS = 1e-6  # fla's l2norm: x * rsqrt(sum(x^2) + eps)
# v5e: 128 MiB of VMEM a core; the kernel holds two buffers each of a
# row's state in and out (2.2 MB each at 96 x 5760)
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def l2norm(x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + _NORM_EPS)


def gates(a, b, a_log, dt_bias, allow_neg_eigval: bool):
    """Projections a, b [..., H] -> (g = log decay <= 0, beta), float32."""
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias.astype(jnp.float32)
    )
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    return g, beta * 2.0 if allow_neg_eigval else beta


def fold(s: jax.Array) -> jax.Array:
    """[..., H, dk, dv] -> the device layout [..., dk, H * dv]."""
    *lead, h, dk, dv = s.shape
    return jnp.moveaxis(s, -3, -2).reshape(*lead, dk, h * dv)


def unfold(s: jax.Array, h: int) -> jax.Array:
    """The device layout [..., dk, H * dv] -> [..., H, dk, dv]."""
    *lead, dk, hv = s.shape
    return jnp.moveaxis(s.reshape(*lead, dk, h, hv // h), -2, -3)


def gated_delta_recurrent(q, k, v, g, beta, s0):
    """Token by token. q, k [B, S, H, dk]; v [B, S, H, dv]; g, beta
    [B, S, H]; s0 [B, dk, H * dv] float32 -> (o [B, S, H * dv] float32,
    the final state in s0's layout)."""
    h = q.shape[2]
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)  # noqa: E731

    def step(s, inputs):
        qt, kt, vt, gt, bt = inputs  # [B, H, ...]
        s = s * jnp.exp(gt)[..., None, None]
        u = jnp.einsum("bhkv,bhk->bhv", s, kt, precision="highest")
        s = s + kt[..., :, None] * ((vt - u) * bt[..., None])[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision="highest")

    s, o = lax.scan(step, unfold(s0.astype(jnp.float32), h), tuple(map(f32, (q, k, v, g, beta))))
    o = jnp.moveaxis(o, 0, 1)
    return o.reshape(*o.shape[:2], -1), fold(s)


def _unit_lower_inverse(a):
    """(I + a)^-1 for ``a`` [..., C, C] strictly lower triangular, C a power
    of two, by the block recursion of a triangular inverse: with the inverses
    T11, T22 of two neighbouring diagonal blocks of size s, the block of
    size 2s has -T22 M21 T11 below them. Each level is two products of whole
    [C, C] matrices under a mask (T is block diagonal, so T (a * mask) T is
    exactly those sub-blocks): log2 C levels of matrix products, where
    XLA's triangular solve walks the rows one after another (9% of the
    device's time in a decode-drain window on a v5e, PERF.md section 6, PR 32)."""
    c = a.shape[-1]
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    t = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    s = 1
    while s < c:
        below = (i // (2 * s) == j // (2 * s)) & (i % (2 * s) >= s) & (j % (2 * s) < s)
        m21 = jnp.where(below, a, 0.0)
        t = t - jnp.matmul(jnp.matmul(t, m21, precision="highest"), t, precision="highest")
        s *= 2
    return t


def gated_delta_chunk_prefill(q, k, v, g, beta, s0, chunk: int = CHUNK):
    """The same as ``gated_delta_recurrent`` in chunks (the WY form).
    With A the strictly lower part of b_i (k_i . k_j) exp(G_i - G_j) (G the
    running sum of g inside the chunk) and T = (I + A)^-1, the chunk's
    writes are T (b v) - T (b k exp(G)) S: the state enters once a chunk."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-s // chunk)
    pad = n * chunk - s

    def chunks(x):  # [B, S, H, ...] -> [N, B, H, C, ...], float32, zero-padded
        x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    with jax.named_scope("gated_delta_chunk_prefill"):
        qc, kc, vc = chunks(q), chunks(k), chunks(v)  # [N, B, H, C, d]
        gc, bc = chunks(g), chunks(beta)  # [N, B, H, C]; padding: g 0, beta 0
        cum = jnp.cumsum(gc, axis=-1)
        # exp(G_i - G_j) for i >= j: never above 1
        lower = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :], 0.0)), 0.0)
        kb = kc * bc[..., None]
        a = jnp.einsum("...ik,...jk->...ij", kb, kc) * decay
        a = jnp.where(jnp.tril(jnp.ones((chunk, chunk), jnp.bool_), -1), a, 0.0)
        t = _unit_lower_inverse(a)
        u = t @ (vc * bc[..., None])  # [N, B, H, C, dv]
        w = t @ (kb * jnp.exp(cum)[..., None])  # [N, B, H, C, dk]
        qk = jnp.einsum("...ik,...jk->...ij", qc, kc) * decay  # the diagonal included
        q_in = qc * jnp.exp(cum)[..., None]
        k_out = kc * jnp.exp(cum[..., -1:] - cum)[..., None]
        last = jnp.exp(cum[..., -1])  # [N, B, H]

        def step(state, inputs):  # state [B, H, dk, dv]
            u_i, w_i, qk_i, q_i, k_i, last_i = inputs
            v_new = u_i - w_i @ state
            o_i = q_i @ state + qk_i @ v_new
            state = state * last_i[..., None, None] + jnp.swapaxes(k_i, -1, -2) @ v_new
            return state, o_i

        state, o = lax.scan(step, unfold(s0.astype(jnp.float32), h), (u, w, qk, q_in, k_out, last))
        # [N, B, H, C, dv] -> [B, S, H * dv]
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * chunk, h * dv)
        return o[:, :s], fold(state)


# ---------------------------------------------------------------------------
# Decode: one token a row, the state updated where it lies
# ---------------------------------------------------------------------------


def gated_delta_update_jnp(q, k, v, g, beta, state, layer, rows, live):
    """One step a row on the whole model's state [L, R, dk, H * dv]:
    batch row i owns state row ``rows[i]`` of ``layer``; a row that is not
    ``live`` keeps its state and gives zeros. q, k [B, H, dk]; v [B, H, dv];
    g, beta [B, H] -> (o [B, H * dv] float32, state)."""
    s0 = state.at[layer, rows].get(mode="clip")
    o, s1 = gated_delta_recurrent(
        q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None], s0
    )
    # an idle row's write goes out of bounds and drops
    state = state.at[layer, jnp.where(live, rows, state.shape[1])].set(
        s1.astype(state.dtype), mode="drop"
    )
    return jnp.where(live[:, None], o[:, 0], 0.0), state


def _update_kernel(
    srow_ref,  # scalar prefetch [B]: the state row (layer's offset added) of step b
    live_ref,  # scalar prefetch [B]
    nlive_ref,  # scalar prefetch [1]
    kq_ref,  # [1, 2 * dk, H]: k then q, one column a head
    vec_ref,  # [1, 3, H * dv]: v, the decay and beta, each head's over its lanes
    s_in,  # [1, dk, H * dv]
    o_ref,  # [1, 1, H * dv]
    s_out,  # [1, dk, H * dv], the same memory as s_in
    *,
    tile: int,
    dv: int,
):
    del srow_ref
    b = pl.program_id(0)
    dk, hv = s_in.shape[1], s_in.shape[2]
    live = live_ref[b] == 1

    def over_lanes(rows, lo):
        """A head's k (or q) column over that head's lanes of the tile at
        ``lo``: a tile holds the lanes of one head or of a few (all static)."""
        first, last = lo // dv, (lo + tile - 1) // dv
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) + lo
        out = jnp.broadcast_to(kq_ref[0, rows, first : first + 1], (dk, tile))
        for head in range(first + 1, last + 1):
            col = jnp.broadcast_to(kq_ref[0, rows, head : head + 1], (dk, tile))
            out = jnp.where(lane >= head * dv, col, out)
        return out

    @pl.when(live)
    def _():
        for lo in range(0, hv, tile):  # unrolled: every slice is static
            at = pl.ds(lo, tile)
            k_x, q_x = over_lanes(pl.ds(0, dk), lo), over_lanes(pl.ds(dk, dk), lo)
            vec = vec_ref[0, :, at]
            s = s_in[0, :, at].astype(jnp.float32) * vec[1:2]
            u = jnp.sum(s * k_x, axis=0, keepdims=True)
            s = s + k_x * ((vec[0:1] - u) * vec[2:3])
            s_out[0, :, at] = s.astype(s_out.dtype)
            o_ref[0, :, at] = jnp.sum(s * q_x, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # an idle step names the block of a live neighbour (`_live_row_map`), so
    # nothing of it is fetched or written back; with no live row at all
    # every step names block 0, which then has to come back as it went in
    @pl.when((nlive_ref[0] == 0) & (b == 0))
    def _():
        s_out[...] = s_in[...]


def _live_row_map(live: jax.Array) -> jax.Array:
    """For each batch row the row whose state block its grid step names: its
    own if live, else the last live row before it, else the first live row
    after it, else row 0. Consecutive steps that name one block fetch and
    write it back once, so an idle row costs no state traffic."""
    n = live.shape[0]
    idx = jnp.arange(n)
    before = lax.cummax(jnp.where(live, idx, -1))
    first = jnp.argmax(live)  # 0 where none is live
    return jnp.where(before >= 0, before, first).astype(jnp.int32)


def gated_delta_update(q, k, v, g, beta, state, layer, rows, live, interpret: bool = False):
    """``gated_delta_update_jnp`` as a Pallas kernel. The whole state is
    aliased to the result and seen as [L * R, dk, H * dv]; only live rows'
    blocks move. Live rows have distinct ``rows`` inside the state."""
    b, h, dk = q.shape
    n_layers, n_rows, _, hv = state.shape
    tile = 128 if hv % 128 == 0 else hv
    live = live.astype(jnp.bool_)
    step_row = _live_row_map(live)
    srow = (jnp.asarray(layer, jnp.int32) * n_rows + jnp.clip(rows[step_row], 0, n_rows - 1)).astype(jnp.int32)
    # k and q, a column a head: [B, 2 * dk, Hp]
    kq = jnp.swapaxes(jnp.concatenate([k, q], axis=-1).astype(jnp.float32), 1, 2)
    dv = hv // h
    over_lanes = lambda x: jnp.repeat(x.astype(jnp.float32), dv, axis=-1)  # noqa: E731
    vec = jnp.stack(
        [v.astype(jnp.float32).reshape(b, hv), over_lanes(jnp.exp(g)), over_lanes(beta)], axis=1
    )
    flat = state.reshape(n_layers * n_rows, dk, hv)
    row = lambda i, srow, live, nlive: (i, 0, 0)  # noqa: E731
    own = lambda i, srow, live, nlive: (srow[i], 0, 0)  # noqa: E731
    note_kernel("gated_delta_update")
    o, flat = pl.pallas_call(
        functools.partial(_update_kernel, tile=tile, dv=dv),
        name="gated_delta_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, 2 * dk, h), row),
                pl.BlockSpec((1, 3, hv), row),
                pl.BlockSpec((1, dk, hv), own),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hv), row),
                pl.BlockSpec((1, dk, hv), own),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, hv), jnp.float32),
            jax.ShapeDtypeStruct(flat.shape, flat.dtype),
        ],
        # operand 5 (after the three prefetched scalars, kq and vec) is the
        # state: updated in place
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )(
        srow, live.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32).reshape(1),
        kq, vec, flat,
    )
    return o[:, 0], flat.reshape(state.shape)


def gated_delta_pallas_ok(config: ModelConfig) -> bool:
    """Whether the decode update runs as the kernel: ``attention_impl`` as
    for the attention kernels ("jnp" never, "pallas" always, in interpret
    mode off the TPU; "auto" on a TPU where the folded state is whole lanes
    and whole sublanes)."""
    if config.attention_impl == "jnp" or config.kernel_mesh is not None:
        return False
    if config.attention_impl == "pallas":
        return True
    return (
        jax.default_backend() == "tpu"
        and config.linear_value_dim % 128 == 0
        and config.linear_key_head_dim % 8 == 0
    )
