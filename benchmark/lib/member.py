#!/usr/bin/env python3
"""The member's launcher: exactly `python -m etcd_tpu <args>`, plus one
daemon thread that starts and stops the JAX profiler when the runner asks.

Only the process that holds the chip can trace it, and the program offers no
way to ask from outside, so the benchmark starts the member through this
file. With `--trace 0` the runner does not set ETCD_BENCH_TRACE_CTL and no
thread is started: the process is then `etcd_tpu.etcdmain.main(argv)` and
nothing else.

Control files in $ETCD_BENCH_TRACE_CTL (the runner creates the first and the
third, this thread the others):
  trace.start  -> jax.profiler.start_trace(<ctl>/trace)   -> trace.started
  trace.stop   -> jax.profiler.stop_trace()               -> trace.done
trace.done holds {"window_s": seconds between the two calls, on this
process's monotonic clock}.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE_CTL_ENV = "ETCD_BENCH_TRACE_CTL"


def _wait_for(path: str) -> None:
    while not os.path.exists(path):
        time.sleep(0.01)


def _touch(path: str, doc: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


def _trace_control(ctl: str) -> None:
    _wait_for(os.path.join(ctl, "trace.start"))
    import jax

    opts = jax.profiler.ProfileOptions()
    # No Python tracer: with a thread per connection it costs more than
    # everything it would explain. Host TraceMe events (PjRt execute and
    # transfers) stay on.
    opts.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(ctl, "trace"),
                             profiler_options=opts)
    t0 = time.monotonic()
    _touch(os.path.join(ctl, "trace.started"), {"t0": t0})
    _wait_for(os.path.join(ctl, "trace.stop"))
    t1 = time.monotonic()
    jax.profiler.stop_trace()
    _touch(os.path.join(ctl, "trace.done"),
           {"window_s": t1 - t0, "stop_s": time.monotonic() - t1})


def main() -> int:
    # sys.path[0] is this file's directory: the program must not find the
    # benchmark's modules, only the checkout's root.
    sys.path[0] = ROOT
    ctl = os.environ.get(TRACE_CTL_ENV)
    if ctl:
        threading.Thread(target=_trace_control, args=(ctl,), daemon=True,
                         name="bench-trace-ctl").start()
    from etcd_tpu.etcdmain import main as etcd_main

    return etcd_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
