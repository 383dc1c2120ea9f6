"""A run of a cell at a size a test can hold, without a chip.

    python benchmark/tests/rehearsal.py --workload msmarco-serial --seed 5 --seconds 5 --trace 0
    REHEARSE_DEVICES=4 python benchmark/tests/rehearsal.py --workload msmarco-serial ...

Everything but the look for a chip is the harness's own: the device
check is patched from outside, the corpus is cut to ``REHEARSE_DOCS``
(2,000) documents, warm-up to a few hundred requests, and a cell can be
given four virtual CPU devices to rehearse ``chips: 4``. Times from here
are the sandbox's and are never a device metric.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def prepare(devices: int = 1) -> None:
    """Environment of a rehearsal; before JAX is first imported."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["ES_TPU_PALLAS"] = "interpret"
    if devices > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
    if BENCH not in sys.path:
        sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


@contextlib.contextmanager
def patched(docs: int, devices: int, pool: int = 20000):
    """The harness with its chip check, its sizes and its table of peaks
    steered from outside; restored on exit."""
    import jax

    from harness import run_cell, server, work

    saved = (server.require_tpu, run_cell.load_cell, work.peaks_for,
             server.CACHE)

    def load(manifest, workload):
        cell, config, traffic = saved[1](manifest, workload)
        config["docs"] = docs
        if "append_pool_docs" in config["generator_params"]:
            config["generator_params"]["append_pool_docs"] = pool
        traffic["warmup"]["min_requests"] = min(
            200, traffic["warmup"].get("min_requests", 0))
        return dict(cell, chips=devices), config, traffic

    server.require_tpu = lambda chips: jax.devices()[:chips]
    run_cell.load_cell = load
    work.peaks_for = lambda kind: saved[2]("TPU v5 lite")
    server.CACHE = os.path.join(server.CACHE, "rehearsal")
    try:
        yield
    finally:
        (server.require_tpu, run_cell.load_cell, work.peaks_for,
         server.CACHE) = saved


def run(argv, docs: int = 2000, devices: int = 1):
    """(exit code, result or None, standard error) of one rehearsed run."""
    import run as bench_run

    out, err = io.StringIO(), io.StringIO()
    with patched(docs, devices), contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(err):
        code = bench_run.main(argv)
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1]) if code == 0 and lines else None
    return code, result, err.getvalue() + out.getvalue()


if __name__ == "__main__":
    n_dev = int(os.environ.get("REHEARSE_DEVICES", "1"))
    prepare(n_dev)
    import run as bench_run

    with patched(int(os.environ.get("REHEARSE_DOCS", "2000")), n_dev):
        sys.exit(bench_run.main(sys.argv[1:]))
