"""Record the small trace that ``testdata/`` keeps, on the chip: a few steps
of a loop shaped like ``run.py``'s window (a jitted step with a matrix
product and an elementwise pass, two steps in flight, the ``bench.*`` spans),
with a pause planted between two calls so that there is a gap to attribute.
On a host of several chips the step runs on all of them and exchanges what a
data-parallel step does: an all-reduce, and a reduce-scatter with its
all-gather. Writes ``chiprun_out/recorded_trace_<chips>/`` and prints what the
trace holds, every collective event among it."""

import collections
import glob
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp

    from benchmarks import trace_reduce

    chips = len(jax.devices())
    out = os.path.join(ROOT, "chiprun_out", f"recorded_trace_{chips}")

    def one_chip(x):
        y = jnp.tanh(x @ x) * 0.5
        return y, jnp.mean(y)

    def every_chip(x):
        y, _ = one_chip(x)
        y = jax.lax.psum(y, "chips") / chips                       # all-reduce
        part = jax.lax.psum_scatter(y, "chips", tiled=True)        # scatter
        y = jax.lax.all_gather(part, "chips", tiled=True) / chips  # gather
        return y, jax.lax.pmean(jnp.mean(y), "chips")

    if chips == 1:
        step = jax.jit(one_chip)
        x = jnp.ones((1024, 1024), jnp.bfloat16)
    else:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(jax.devices(), ("chips",))
        step = jax.jit(jax.shard_map(every_chip, mesh=mesh, in_specs=P("chips"),
                                     out_specs=(P("chips"), P())))
        x = jax.device_put(jnp.ones((chips * 1024, 1024), jnp.bfloat16),
                           NamedSharding(mesh, P("chips")))
    x, loss = step(x)
    float(loss)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    pending = collections.deque()
    for i in range(6):
        with jax.profiler.TraceAnnotation("bench.run_step"):
            x, loss = step(x)
        pending.append(loss)
        if len(pending) > 2:
            with jax.profiler.TraceAnnotation("bench.wait_loss"):
                float(pending.popleft())
        if i == 3:
            time.sleep(0.02)
    with jax.profiler.TraceAnnotation("bench.drain"):
        [float(p) for p in pending]
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    print(path, os.path.getsize(path), "bytes")
    profile = jax.profiler.ProfileData.from_file(path)
    for plane in profile.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            events = list(line.events)
            print("  line", repr(line.name), len(events), "events")
            for e in events[:6]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns)
            for e in events:
                if trace_reduce.COLLECTIVE.match(
                        trace_reduce.operation(e.name)) and \
                        plane.name.startswith("/device:"):
                    print("      collective", repr(e.name[:60]), e.start_ns,
                          e.duration_ns)
    print(trace_reduce.reduce(profile, chips))


if __name__ == "__main__":
    main()
