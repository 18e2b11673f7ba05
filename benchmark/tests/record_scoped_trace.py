"""How `data/tpu_scoped.xplane.pb` was recorded, and what to run to record
it again: on one v5e,

    python benchmark/tests/record_scoped_trace.py

profiles six dispatches of a small program shaped like a decode chunk (a
layer scan inside a step scan, every `jax.named_scope` of the decode
vocabulary, a Pallas kernel with a `name=`), launched one ahead of the fetch
as the engine does, under the engine's `jax.profiler.TraceAnnotation`s. It
prints the planes, lines, event names and stats (where a scope path shows,
and where it does not) and leaves the trace in `chiprun_out/`. Not a test."""
import functools
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl


def _k(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def double(x):
    return pl.pallas_call(_k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype), name="probe_kernel", interpret=jax.default_backend() != "tpu")(x)


@functools.partial(jax.jit, static_argnames=("steps",))
def probe_chunk(w, x, pool, steps):
    def step(carry, _):
        x, pool = carry
        def layer(c, inp):
            x, pool = c
            wl, l = inp
            with jax.named_scope("kv_pool.read"):
                page = lax.dynamic_index_in_dim(pool, l, 0, keepdims=False)
            with jax.named_scope("attention"):
                h = jnp.tanh(x @ wl) + page
                with jax.named_scope("kernel"):
                    h = double(h)
            with jax.named_scope("ffn"):
                h = jax.nn.silu(h @ wl.T) @ wl
            with jax.named_scope("kv_pool.write"):
                pool = lax.dynamic_update_index_in_dim(pool, page + 1.0, l, 0)
            return (x + h, pool), None
        (x, pool), _ = lax.scan(layer, (x, pool), (w, jnp.arange(w.shape[0])))
        with jax.named_scope("head"):
            y = (x @ w[0]).sum(-1)
        with jax.named_scope("sample"):
            t = jnp.argmax(x, -1)
        return (x * 0.5, pool), (y, t)
    (x, pool), out = lax.scan(step, (x, pool), None, length=steps)
    return out, x, pool


def main():
    print(jax.devices())
    # on the chip: ~1.4 ms a step, so an execution outlasts a launch
    L, D, B = (4, 4096, 1024) if jax.default_backend() == "tpu" else (4, 256, 64)
    w = jnp.ones((L, D, D), jnp.bfloat16) * 0.01
    x = jnp.ones((B, D), jnp.bfloat16)
    pool = jnp.zeros((L, B, D), jnp.bfloat16)
    out, x2, pool = probe_chunk(w, x, pool, 4)
    jax.block_until_ready(out)
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    # as the engine does it: dispatch k+1 goes out before k's result is
    # fetched; the profile starts with dispatch 1 in flight and stops with
    # dispatch 6's fetch still to come
    steps_of = [4, 4, 4, 8, 4, 4]
    inflight = []
    def dispatch(seq):
        nonlocal pool
        with jax.profiler.TraceAnnotation("engine.dispatch"):
            with jax.profiler.TraceAnnotation("engine.decode_chunk", seq=seq, steps=steps_of[seq - 1]):
                out, _, pool = probe_chunk(w, x, pool, steps_of[seq - 1])
        inflight.append((seq, out))
    def fetch():
        seq, out = inflight.pop(0)
        with jax.profiler.TraceAnnotation("engine.process.wait"):
            with jax.profiler.TraceAnnotation("engine.fetch", seq=seq):
                np.asarray(out[0])
    jax.block_until_ready(probe_chunk(w, x, pool, 8)[0])
    dispatch(1)
    jax.profiler.start_trace(d, profiler_options=opts)
    for seq in range(2, 7):
        dispatch(seq)
        fetch()
    jax.profiler.stop_trace()
    fetch()
    path = sorted(Path(d).glob("plugins/profile/*/*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    for plane in data.planes:
        print("PLANE", plane.name, "stats:", [(k, str(v)[:80]) for k, v in plane.stats][:10])
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events))
            seen = set()
            interesting = plane.name.startswith("/device:") or any("engine." in e.name for e in events)
            if not interesting:
                continue
            for e in events:
                if e.name in seen or (not plane.name.startswith("/device:") and "engine." not in e.name):
                    continue
                seen.add(e.name)
                if len(seen) > 60:
                    break
                print("    EVENT", repr(e.name[:400]), e.duration_ns)
                print("      STATS", [(k, str(v)[:300]) for k, v in e.stats])
    out_dir = Path("chiprun_out"); out_dir.mkdir(exist_ok=True)
    (out_dir / "tpu_scoped.xplane.pb").write_bytes(path.read_bytes())


if __name__ == "__main__":
    main()
