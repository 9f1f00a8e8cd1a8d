"""Record the small TPU trace that ``test_xplane.py`` reduces.

    python3 chipbench/tests/record_xplane.py   # on a TPU host

Inside the window annotation: two jitted matrix products with a 30 ms
host sleep between them, inside a host annotation ``host_wait``, so the
device idles through it. Writes ``tests/data/window.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> None:
    import jax
    import jax.numpy as jnp
    from chipbench import xplane

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_xplane: needs a TPU")
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.path.join(HERE, "data"))
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("host_wait"):
                time.sleep(0.03)
            f(x).block_until_ready()
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)[0]
        shutil.copy(src, os.path.join(HERE, "data", "window.xplane.pb"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
