"""The one process that holds the chip: pinot_tpu's `StartServer`,
in-process, with a thread that starts and stops jax.profiler when the
harness asks through files in --profile-dir (only the process that holds
the chip can trace it, and the program has no profiler of its own).

  <dir>/go    appears -> start_trace(<dir>/trace)
  <dir>/halt  appears -> stop_trace(), then <dir>/done holds the times

Everything after `--` goes to pinot_tpu.tools.admin unchanged."""
from __future__ import annotations

import json
import os
import sys
import threading
import time


def profiler_thread(ctl: str) -> None:
    go, halt, done = (os.path.join(ctl, n) for n in ("go", "halt", "done"))
    while not os.path.exists(go):
        time.sleep(0.02)
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(os.path.join(ctl, "trace"),
                             profiler_options=opts)
    started = time.time()
    while not os.path.exists(halt):
        time.sleep(0.02)
    stopping = time.time()
    jax.profiler.stop_trace()
    with open(done + ".tmp", "w") as f:
        json.dump({"started_wall": started, "stopping_wall": stopping,
                   "stopped_wall": time.time()}, f)
    os.replace(done + ".tmp", done)


def main(argv: list) -> int:
    split = argv.index("--")
    ctl = argv[argv.index("--profile-dir") + 1]
    os.makedirs(ctl, exist_ok=True)
    threading.Thread(target=profiler_thread, args=(ctl,), daemon=True,
                     name="bench-profiler").start()
    from pinot_tpu.tools import admin
    return admin.main(argv[split + 1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
