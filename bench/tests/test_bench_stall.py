"""The stall probe sees a thread that holds the interpreter lock, and
writes every thread's stack when the stall outlasts its dump delay."""
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import stall_probe  # noqa: E402


def test_a_lock_held_in_c_is_a_stall_with_cpu_time(tmp_path):
    with open(tmp_path / "stacks.txt", "w+") as stacks:
        w = stall_probe.Watcher(stacks)
        w.start()
        time.sleep(0.1)
        sum(range(50_000_000))   # one C call: the lock is never let go
        time.sleep(0.1)
        w.done.set()
        w.join(5)
        stacks.seek(0)
        dumped = stacks.read()
    assert not w.is_alive()
    long = [s for s in w.stalls if s["stall_s"] > 0.5]
    assert long, w.stalls
    assert long[0]["user_s"] + long[0]["sys_s"] > 0.3
    assert "test_a_lock_held_in_c_is_a_stall" in dumped


def test_a_calm_process_shows_no_stall(tmp_path):
    with open(tmp_path / "stacks.txt", "w") as stacks:
        w = stall_probe.Watcher(stacks)
        w.start()
        time.sleep(0.3)
        w.done.set()
        w.join(5)
    assert not [s for s in w.stalls if s["stall_s"] > 0.5]
