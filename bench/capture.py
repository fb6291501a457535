"""Record what the timed path produced, for the comparison after the window.

The harness keeps the program's own outputs as they are made: every
``(mapping, report)`` the evaluator accounts (``evaluate_mapping`` as
``repro.core.dse`` calls it) and every Data-Scheduler schedule solved
(``schedule_many`` and the single-problem scan solve).  Holding references
costs the timed path a dict insert per mapping and per problem batch.
"""

from __future__ import annotations

from contextlib import contextmanager


def sched_key(rows: int, cols: int, sets, chunk: float, link_bw: float,
              freq: float, pj: float) -> tuple:
    return (rows, cols, tuple(tuple(int(n) for n in s) for s in sets),
            float(chunk), float(link_bw), float(freq), float(pj))


class Capture:
    def __init__(self):
        self.mappings: dict = {}    # (hw tuple, graph name) -> (map, report)
        self.schedules: dict = {}   # sched_key -> [cycle, ...]

    def _schedule(self, noc, sets, chunks, link_bw, freq, pj, result):
        key = sched_key(noc.rows, noc.cols, sets, chunks[0], link_bw, freq,
                        pj)
        self.schedules[key] = [list(c) for c in result.cycles]

    @contextmanager
    def installed(self):
        from repro.core import dse
        from repro.engine import scheduler_opt as so
        orig = (dse.evaluate_mapping, so.schedule_many, so._solve_one_scan)

        def evaluate_mapping(mapping, *a, **kw):
            rep = orig[0](mapping, *a, **kw)
            self.mappings[(mapping.hw.as_tuple(), mapping.graph.name)] = (
                mapping, rep)
            return rep

        def schedule_many(problems, link_bw, freq, pj, **kw):
            out = orig[1](problems, link_bw, freq, pj, **kw)
            for (noc, sets, chunks), res in zip(problems, out):
                self._schedule(noc, sets, chunks, link_bw, freq, pj, res)
            return out

        def solve_one_scan(noc, sets, chunks, link_bw, freq, pj, **kw):
            res = orig[2](noc, sets, chunks, link_bw, freq, pj, **kw)
            self._schedule(noc, sets, chunks, link_bw, freq, pj, res)
            return res

        dse.evaluate_mapping = evaluate_mapping
        so.schedule_many = schedule_many
        so._solve_one_scan = solve_one_scan
        try:
            yield self
        finally:
            dse.evaluate_mapping, so.schedule_many, so._solve_one_scan = orig


def neutral_mapping(mapping, report) -> dict:
    """The program's mapping and report as plain tuples and floats."""
    def lm(x):
        return (tuple(x.ph), tuple(x.pw), tuple(x.p_order))

    def reg(r):
        return (r.h_pos, r.w_pos, r.h_shape, r.w_shape)

    def dl(d):
        return (d.order, d.group)
    choices = {n: {"lm": lm(c.lm), "wr": int(c.wr), "region": reg(c.region),
                   "dl_in": dl(c.dl_in), "dl_out": dl(c.dl_out),
                   "perf_s": float(c.perf_s), "size": float(c.size_bytes)}
               for n, c in mapping.choices.items()}
    sm = {int(i): (tuple(reg(r) for r in s.regions), tuple(s.ir))
          for i, s in mapping.sm.items()}
    layers = {l.name: (l.latency_s, l.comm_s, l.energy_pj, l.e_noc_pj)
              for l in report.layers}
    return {"choices": choices, "sm": sm,
            "est_latency_s": float(mapping.est_latency_s), "layers": layers,
            "latency_s": float(report.latency_s),
            "energy_pj": float(report.energy_pj)}
