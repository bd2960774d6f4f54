"""Find where a run's host stalls come from: one benchmark run with a
watcher thread beside it.

    python3 bench/stall_probe.py --stacks stalls.txt -- \\
        --workload dp_short_serve --seed 5 --seconds 51 --trace 0

The watcher wakes every ``TICK_S`` seconds.  A wake-up later than
``MIN_GAP_S`` is a stall; for each it prints one JSON line on standard
error with the stall's length and what the process did meanwhile: CPU
seconds (user and system), page faults, context switches, resident
memory and, where the cgroup shows it, seconds throttled.  CPU time
near the stall's length means a thread held the interpreter lock; near
0 means the process was not running.  The watcher re-arms
``faulthandler.dump_traceback_later`` on every wake-up, so a stall
longer than ``DUMP_AFTER_S`` writes every thread's stack to
``--stacks``: the thread that holds the lock is the one not waiting.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import pathlib
import resource
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
CPU_STAT = pathlib.Path("/sys/fs/cgroup/cpu.stat")
TICK_S = 0.01
MIN_GAP_S = 0.05
DUMP_AFTER_S = 0.5


def throttled_s() -> float | None:
    try:
        for line in CPU_STAT.read_text().splitlines():
            k, v = line.split()
            if k == "throttled_usec":
                return int(v) / 1e6
    except (OSError, ValueError):
        pass
    return None


def rss_mb() -> float:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * resource.getpagesize() / 2**20


class Watcher(threading.Thread):
    def __init__(self, stacks):
        super().__init__(name="stall-watcher", daemon=True)
        self.stacks = stacks
        self.done = threading.Event()
        self.stalls: list[dict] = []

    def run(self) -> None:
        t0 = last = time.perf_counter()
        ru, thr = resource.getrusage(resource.RUSAGE_SELF), throttled_s()
        while not self.done.is_set():
            faulthandler.dump_traceback_later(DUMP_AFTER_S,
                                              file=self.stacks)
            time.sleep(TICK_S)
            now = time.perf_counter()
            ru2, thr2 = resource.getrusage(resource.RUSAGE_SELF), \
                throttled_s()
            if now - last > MIN_GAP_S:
                s = {"at_s": round(now - t0, 6),
                     "stall_s": round(now - last, 6),
                     "user_s": round(ru2.ru_utime - ru.ru_utime, 6),
                     "sys_s": round(ru2.ru_stime - ru.ru_stime, 6),
                     "majflt": ru2.ru_majflt - ru.ru_majflt,
                     "minflt": ru2.ru_minflt - ru.ru_minflt,
                     "nvcsw": ru2.ru_nvcsw - ru.ru_nvcsw,
                     "nivcsw": ru2.ru_nivcsw - ru.ru_nivcsw,
                     "rss_mb": round(rss_mb(), 1)}
                if thr is not None and thr2 is not None:
                    s["throttled_s"] = round(thr2 - thr, 6)
                self.stalls.append(s)
                print("stall " + json.dumps(s), file=sys.stderr,
                      flush=True)
            last, ru, thr = now, ru2, thr2
        faulthandler.cancel_dump_traceback_later()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--stacks", required=True)
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    run_args = args.run_args[1:] if args.run_args[:1] == ["--"] \
        else args.run_args
    sys.path.insert(0, str(ROOT))
    from bench import run
    with open(args.stacks, "w") as stacks:
        w = Watcher(stacks)
        w.start()
        try:
            rc = run.main(run_args)
        finally:
            w.done.set()
            w.join()
    longest = max((s["stall_s"] for s in w.stalls), default=0.0)
    print(f"stalls over {MIN_GAP_S} s: {len(w.stalls)}, longest "
          f"{longest:.6f} s", file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
