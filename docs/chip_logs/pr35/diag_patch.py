"""diag_patch.py <checkout>: a THROW-AWAY copy's tracer also records each span's native thread id and
thread CPU time (attrs ``tid`` / ``cpu``; a span somebody else timed keeps ``cpu`` None), and the
benchmark's launcher also appends every drained span to $KEEP_SPANS — PR 34's call B, made again (the
thread-time reading of the worker's account).  Never applied to a tree that is committed or compared."""
import sys

root = sys.argv[1]


def patch(path, pairs):
    text = open(path).read()
    for old, new in pairs:
        assert text.count(old) == 1, (path, old)
        text = text.replace(old, new)
    open(path, "w").write(text)


patch(root + "/loongcollector_tpu/trace/tracer.py", [
    ('                 "events", "status", "_ended")',
     '                 "events", "status", "_ended", "_cpu0")'),
    ('        self._start_perf = time.perf_counter()\n        self.duration_s: Optional[float] = None',
     '        self._start_perf = time.perf_counter()\n        self._cpu0 = time.thread_time()\n'
     '        self.duration_s: Optional[float] = None'),
    ('        self.duration_s = time.perf_counter() - self._start_perf\n        self.tracer._record(self)',
     '        self.duration_s = time.perf_counter() - self._start_perf\n'
     '        import threading as _th\n'
     '        self.attrs["cpu"] = time.thread_time() - self._cpu0\n'
     '        self.attrs["tid"] = _th.get_native_id()\n'
     '        self.tracer._record(self)'),
    ('        self.duration_s = duration_s\n        self.tracer._record(self, store)',
     '        self.duration_s = duration_s\n'
     '        import threading as _th\n'
     '        self.attrs["tid"] = _th.get_native_id()\n'
     '        self.tracer._record(self, store)'),
])
patch(root + "/perfbench/launcher.py", [
    ('    spans, _events = tracer.drain()\n',
     '    spans, _events = tracer.drain()\n'
     '    keep = os.environ.get("KEEP_SPANS")\n'
     '    if keep and out is not None:\n'
     '        with open(keep, "a") as k:\n'
     '            for s in spans:\n'
     '                k.write(json.dumps([s.name, s._start_perf, s.duration_s or 0.0, s.span_id,\n'
     '                                    s.parent_id, s.attrs], default=str) + "\\n")\n'),
])
print("patched", root)
