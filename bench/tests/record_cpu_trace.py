"""Records the small CPU trace that ``test_trace.py`` reduces: two virtual
CPU devices, a few ``bench.round`` spans, each a matmul step and a ppermute
exchange under ``shard_map``, with a host pause between rounds.

    python3 bench/tests/record_cpu_trace.py

writes ``bench/tests/data/cpu_trace.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                   "cpu_trace.xplane.pb")


def main() -> int:
    mesh = Mesh(jax.devices()[:2], ("data",))

    def worker(x):
        y = jnp.tanh(x @ x.T) @ x
        return jax.lax.ppermute(y, "data", [(0, 1), (1, 0)])

    step = jax.jit(jax.shard_map(worker, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data")))
    x = jnp.ones((2 * 256, 256), jnp.float32)
    step(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(4):
        with jax.profiler.TraceAnnotation("bench.round"):
            with jax.profiler.TraceAnnotation("bench.feed"):
                time.sleep(0.002)
            step(x).block_until_ready()
        time.sleep(0.003)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, OUT)
    shutil.rmtree(d)
    print(OUT, os.path.getsize(OUT))
    return 0


if __name__ == "__main__":
    sys.exit(main())
