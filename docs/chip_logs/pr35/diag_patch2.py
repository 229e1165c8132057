"""diag_patch2.py <checkout>: on top of diag_patch.py, finer THROW-AWAY spans inside the list program's two
legs (``x.*``), to say where the stage spans' self time goes: the source columns, the routing sum, the
owner's buffers, the chunk gathers, the delivery, and the pieces of the install."""
import sys

root = sys.argv[1]


def patch(path, pairs):
    text = open(path).read()
    for old, new in pairs:
        assert text.count(old) == 1, (path, old)
        text = text.replace(old, new)
    open(path, "w").write(text)


patch(root + "/loongcollector_tpu/processor/grok.py", [
    ('        src = extract_source(group, self.source_key)\n        if src is None or not len(src.offsets):\n            return None\n        if not src.columnar:',
     '        with stage_span("x.extract_source"):\n            src = extract_source(group, self.source_key)\n'
     '        if src is None or not len(src.offsets):\n            return None\n        if not src.columnar:'),
    ('        route = self._list_route(src) if self._list_ok else None\n',
     '        with stage_span("x.list_route"):\n            route = self._list_route(src) if self._list_ok else None\n'),
    ('        pending = PendingMatchList(kernel, src.arena, src.offsets,\n                                   src.lengths)\n',
     '        with stage_span("x.pending_init"):\n            pending = PendingMatchList(kernel, src.arena, src.offsets,\n'
     '                                       src.lengths)\n'),
    ('        try:\n            res = pending.result()\n        except BaseException:\n            pending.abandon()\n            raise\n        if pending.failed:',
     '        try:\n            with stage_span("x.result"):\n                res = pending.result()\n        except BaseException:\n'
     '            pending.abandon()\n            raise\n        if pending.failed:'),
    ('        matched = member >= 0\n        cols.set_fields_matrix(self._keys, off_mat, len_mat)\n',
     '        matched = member >= 0\n        with stage_span("x.apply.set_fields_matrix"):\n'
     '            cols.set_fields_matrix(self._keys, off_mat, len_mat)\n'),
    ('            fail = ~matched if n_present == n else ~matched & src.present\n'
     '            cols.set_field(self.renamed_source_key,\n                           src.offsets.astype(np.int32),\n'
     '                           np.where(fail, src.lengths, np.int32(-1)))\n',
     '          with stage_span("x.apply.rawlog"):\n'
     '            fail = ~matched if n_present == n else ~matched & src.present\n'
     '            cols.set_field(self.renamed_source_key,\n                           src.offsets.astype(np.int32),\n'
     '                           np.where(fail, src.lengths, np.int32(-1)))\n'),
])
patch(root + "/loongcollector_tpu/ops/regex/engine.py", [
    ('        lane = chip_lanes.current_lane()\n        window = self._window = DevicePlane.instance().open_stream(\n'
     '            self.depth, program=self.program, lane=lane,\n            recover=self._recover, deliver=self._deliver)\n',
     '        from ...processor.common import stage_span\n        lane = chip_lanes.current_lane()\n'
     '        with stage_span("x.open_stream"):\n'
     '            window = self._window = DevicePlane.instance().open_stream(\n'
     '                self.depth, program=self.program, lane=lane,\n                recover=self._recover, deliver=self._deliver)\n'),
    ('                window.submit_rows(call, self.arena, self.offsets[chunk],\n                                   self.lengths[chunk], tag=chunk,\n                                   kernel=kern)\n',
     '                with stage_span("x.gather"):\n                    o_, l_ = self.offsets[chunk], self.lengths[chunk]\n'
     '                with stage_span("x.submit_rows"):\n                    window.submit_rows(call, self.arena, o_, l_, tag=chunk, kernel=kern)\n'),
    ('                self.ok, self.cap_len = member[:n], k_len[:n]\n                self.cap_off = k_off[:n] + batch.origins[:n, None]\n',
     '                from ...processor.common import stage_span\n                with stage_span("x.deliver"):\n'
     '                    self.ok, self.cap_len = member[:n], k_len[:n]\n                    self.cap_off = k_off[:n] + batch.origins[:n, None]\n'),
])
print("patched (2)", root)
