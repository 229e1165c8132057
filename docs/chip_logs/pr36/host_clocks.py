#!/usr/bin/env python3
"""What the machine's kernel gives a thread about itself, and what asking costs: the resolution and
the cost of time.thread_time() (CLOCK_THREAD_CPUTIME_ID) beside perf_counter, alone and beside two
busy Python threads, and the files of /proc/self/task/<tid> that /debug/status `threads` reads."""

import os
import platform
import threading
import time
import timeit


def cost(stmt, n=20000):
    return min(timeit.repeat(stmt, globals=globals(), number=n, repeat=5)) / n * 1e9


def main():
    print("platform", platform.platform(), "| /proc/version:",
          (open("/proc/version").read().strip() if os.path.exists("/proc/version") else None))
    print("cpus", sorted(os.sched_getaffinity(0)))
    for clock in ("thread_time", "perf_counter", "process_time", "monotonic"):
        print("clock_info", clock, time.get_clock_info(clock))
    print("clock_getres CLOCK_THREAD_CPUTIME_ID", time.clock_getres(time.CLOCK_THREAD_CPUTIME_ID))
    for stmt in ("time.thread_time()", "time.perf_counter()", "time.process_time()",
                 "threading.get_native_id()", "os.getppid()"):
        print(f"cost alone ns/call {stmt}: {cost(stmt):.0f}")
    # the clock's steps while this thread spins for 0.3 s of wall time
    seen, t_end = [], time.perf_counter() + 0.3
    last = time.thread_time()
    while time.perf_counter() < t_end:
        now = time.thread_time()
        if now != last:
            seen.append(round(now - last, 6))
            last = now
    print("thread_time steps in a 0.3 s spin:", len(seen), "distinct", sorted(set(seen))[:8])
    stop = threading.Event()

    def spin():
        x = 0
        while not stop.is_set():
            x += 1
    others = [threading.Thread(target=spin, daemon=True) for _ in range(2)]
    for t in others:
        t.start()
    for stmt in ("time.thread_time()", "time.perf_counter()"):
        print(f"cost beside two busy threads ns/call {stmt}: {cost(stmt, 5000):.0f}")
    stop.set()
    tid = threading.get_native_id()
    base = f"/proc/self/task/{tid}"
    print("task dir", sorted(os.listdir(base)) if os.path.isdir(base) else None)
    for name in ("schedstat", "stat", "status"):
        try:
            text = open(f"{base}/{name}").read()
        except OSError as e:
            text = f"<{e}>"
        if name == "status":
            text = [ln for ln in text.splitlines() if "ctxt" in ln or ln.startswith("Cpus_allowed_list")]
        print(name, repr(text)[:400])


if __name__ == "__main__":
    main()
