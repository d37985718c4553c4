"""Time a server worker held a request without running on a CPU, in
milliseconds, averaged over the window's requests: each ``serve.execute``
span's duration (pickup to done, on the worker's thread) less the thread
CPU time it spent (``cpu_ms``).  What is left is waiting: for the
interpreter lock, for other threads, for the device.  Reads nothing where
the program has no such span."""


def read(view):
    stalls = [sp.duration_s * 1e3 - sp.attrs["cpu_ms"]
              for tr in view.traces for sp in tr.find("serve.execute")
              if "cpu_ms" in sp.attrs]
    if not stalls:
        return None
    return sum(stalls) / len(stalls)
