"""Wake-up latency between two threads of one process on this host (no device, no jax):
an Event ping-pong (futex wake + schedule) and a lock hand-over via a 50 us release."""
import threading, time, statistics
N = 20000
a, b = threading.Event(), threading.Event()
def pong():
    for _ in range(N):
        a.wait(); a.clear(); b.set()
th = threading.Thread(target=pong); th.start()
t0 = time.perf_counter()
for _ in range(N):
    a.set(); b.wait(); b.clear()
dt = time.perf_counter() - t0
th.join()
print(f"event ping-pong: {dt / N / 2 * 1e6:.1f} us per wake-up")
# GIL hand-over: thread X spins in Python, thread Y releases the GIL for ~50 us (sleep) and times
# how long it takes to come back
stop = False
def spin():
    x = 0
    while not stop:
        x += 1
th = threading.Thread(target=spin); th.start()
lat = []
for _ in range(2000):
    t = time.perf_counter(); time.sleep(0.00005); lat.append(time.perf_counter() - t)
stop = True; th.join()
alone = []
for _ in range(2000):
    t = time.perf_counter(); time.sleep(0.00005); alone.append(time.perf_counter() - t)
print(f"sleep(50us) alone: median {statistics.median(alone)*1e6:.0f} us; beside a spinning thread: median {statistics.median(lat)*1e6:.0f} us, p90 {sorted(lat)[1800]*1e6:.0f} us")
