"""Record the small TPU traces that ``test_trace.py`` reads.

    python bench/tests/record_trace.py <out_dir> [--chips 4]

Inside a ``bench.window`` span, three ``bench.request`` spans each run a
small jitted loop to completion, and between them a ``bench.client``
span sleeps 50 ms with the devices idle.  On one chip the loop is a
matmul; it writes ``<out_dir>/sample.xplane.pb``.  With ``--chips 4`` the
loop runs on a 1-D mesh of four chips, each trip a halo exchange
(``ppermute`` to the next chip) and a dot summed over the chips
(``psum``), as a distributed CG trip has; it writes
``<out_dir>/sample_x4.xplane.pb``.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
import time
from pathlib import Path

SLEEP_S = 0.05
REQUESTS = 3
TRIPS = 8


def matmul_loop():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        return jax.lax.fori_loop(0, TRIPS, lambda i, y: jnp.tanh(y @ y), x)

    return work, jnp.ones((512, 512), jnp.float32) / 512


def exchange_program(mesh):
    """A loop over the chips of a 1-D ``mesh``: each trip sends a slice to
    the next chip and sums a dot over all of them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    k = mesh.size
    ring = [(i, (i + 1) % k) for i in range(k)]

    def trip(i, y):
        halo = jax.lax.ppermute(y[:128], "pu", ring)
        dot = jax.lax.psum(jnp.vdot(y, y), "pu")
        return jnp.tanh(y.at[:128].add(halo) / jnp.sqrt(dot))

    @jax.jit
    def work(x):
        return jax.shard_map(lambda y: jax.lax.fori_loop(0, TRIPS, trip, y),
                             mesh=mesh, in_specs=P("pu"),
                             out_specs=P("pu"))(x)

    return work


def exchange_loop(devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(devices, ("pu",))
    x = jax.device_put(jnp.ones((len(devices) * 4096,), jnp.float32),
                       NamedSharding(mesh, P("pu")))
    return exchange_program(mesh), x


def record(work, x, log_dir: str) -> None:
    import jax
    from jax.profiler import TraceAnnotation

    work(x).block_until_ready()
    jax.profiler.start_trace(log_dir)
    with TraceAnnotation("bench.window"):
        for i in range(REQUESTS):
            with TraceAnnotation("bench.request"):
                work(x).block_until_ready()
            if i < REQUESTS - 1:
                with TraceAnnotation("bench.client"):
                    time.sleep(SLEEP_S)
    jax.profiler.stop_trace()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("out_dir")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"record_trace: needs {args.chips} TPU chips, found "
              f"{len(devices)} {devices[0].platform!r} devices",
              file=sys.stderr)
        return 1
    if args.chips == 1:
        work, x = matmul_loop()
        name = "sample.xplane.pb"
    else:
        work, x = exchange_loop(devices[:args.chips])
        name = "sample_x4.xplane.pb"
    log_dir = tempfile.mkdtemp()
    try:
        record(work, x, log_dir)
        src = next(Path(log_dir).rglob("*.xplane.pb"))
        Path(args.out_dir).mkdir(parents=True, exist_ok=True)
        shutil.copy(src, Path(args.out_dir) / name)
    finally:
        shutil.rmtree(log_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main())
