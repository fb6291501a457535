"""The comparison that decides ``correct``.

For a sample of the design points the window evaluated, the reference
(``bench/reference``) re-derives, independently of the program:

* ``area_rel``: the logic-die area of every observation of the window;
* ``mapping_rel``: the mapper's result: per layer, the DP cost of the
  program's chosen candidate (node latency costed on the device, plus the
  ring estimate) against the reference's own optimum, and the DP
  objective.  A different choice of equal cost (an exact tie) reads 0; a
  worse choice, or a wrongly costed one, reads its gap;
* ``sched_rel``: each layer's scheduled NoC latency and energy against a
  recomputation from the program's own Hamilton cycles (which must be
  cycles over exactly their sharing sets), and each schedule's hottest
  link against the optimum of a small single set (exhaustive) or the best
  of the search's deterministic starting schedules, which the search never
  makes worse;
* ``result_rel``: each layer's and each observation's latency, energy and
  EDP.

The search's quality is read beside them (:func:`search_quality`), not
judged: the program's search seldom leaves its best start, so no limit
separates a sound run from a search that returns its start (PERF.md).

Relative gaps are ``|a - b| / max(|a|, |b|)``; an observation no recorded
mapping accounts for, or a schedule that is not a set of cycles, reads
``inf``.  Each number is held to its limit in ``bench/limits``.
"""

from __future__ import annotations

import math

import numpy as np

from reference.mapper import LM, Choice, Mapper, Region, part_layer
from reference.model import DL, Graph, Hw, area_mm2, node_cost
from reference import noc as rnoc

from capture import sched_key

NUMBERS = ("area_rel", "mapping_rel", "sched_rel", "result_rel")


def rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(a), abs(b))


def max_load_of(m, cycles, chunk) -> float:
    return rnoc.max_load(m, rnoc.transfers(cycles, [chunk] * len(cycles)))


def _choice_key(c: dict) -> tuple:
    return (c["lm"], c["wr"], c["region"], c["dl_in"], c["dl_out"])


def _ref_choice(c: dict) -> Choice:
    return Choice(LM(*c["lm"]), c["wr"], DL(*c["dl_in"]), DL(*c["dl_out"]),
                  Region(*c["region"]), c["perf_s"], c["size"])


def _neutral(m) -> dict:
    return {"choices": {n: {"lm": (c.lm.ph, c.lm.pw, c.lm.p_order),
                            "wr": c.wr,
                            "region": (c.region.h_pos, c.region.w_pos,
                                       c.region.h_shape, c.region.w_shape),
                            "dl_in": (c.dl_in.order, c.dl_in.group),
                            "dl_out": (c.dl_out.order, c.dl_out.group),
                            "perf_s": c.perf_s, "size": c.size_bytes}
                        for n, c in m.choices.items()},
            "sm": {i: (tuple((r.h_pos, r.w_pos, r.h_shape, r.w_shape)
                             for r in s.regions), s.ir)
                   for i, s in m.sm.items()},
            "est_latency_s": m.est_latency_s}


class Reference:
    """The reference for one configuration file."""

    def __init__(self, config: dict, dt=np.float64):
        self.config = config
        self.constants = config["constants"]
        self.graph = Graph.from_config(config["graph"])
        self.segments = self.graph.segments()
        self.dt = dt
        self.alpha = config["cost_exponents"]["alpha"]
        self.beta = config["cost_exponents"]["beta"]
        self.gamma = config["cost_exponents"]["gamma"]

    def hw(self, values) -> Hw:
        return Hw.make(values, self.constants)

    def mapping(self, values) -> dict:
        m = Mapper(self.hw(values), max_optim_iter=self.config[
            "max_optim_iter"], lm_cap=self.config["lm_cap"],
            n_wr=self.config["n_wr"], cap_units=self.config["cap_units"],
            dl_max_group=self.config["dl_max_group"], dt=self.dt)
        return _neutral(m.map(self.graph))

    def layer_problems(self, hw: Hw, name: str, c: dict) -> list:
        layer = self.graph.layer[name]
        ch = _ref_choice(c)
        pl = part_layer(layer, ch.lm)
        dbytes = hw.c("data_bits") // 8
        shape = (ch.region.h_shape, ch.region.w_shape)
        return shape, rnoc.sharing_problems(
            ch.lm, shape, ch.wr, pl.weight_count * dbytes,
            pl.ifmap_count * dbytes,
            pl.ofmap_count * (hw.c("psum_bits") // 8))

    def evaluate(self, values, choices: dict, schedules: dict,
                 numbers: dict | None = None, fallback: bool = False) -> dict:
        """Latency/energy of a mapping, with the program's schedules.

        Returns per layer ``(lat, comm_lat, energy, comm_en)`` and the
        totals, and keeps the worst excess of a schedule over its bound in
        ``numbers["sched_rel"]``.  A
        small single set the program solved outside the recorded paths is
        solved exhaustively here; with ``fallback`` (the control) any other
        unrecorded problem takes the reference's best starting schedule.
        """
        dt = self.dt
        hw = self.hw(values)
        link_bw, freq = hw.link_bw_bytes, hw.c("freq_hz")
        pj = hw.c("noc_energy_pj_per_bit_hop")
        layers, total_lat, total_en = {}, 0.0, 0.0
        for seg in self.segments:
            region_lat: dict = {}
            for branch in seg.branches:
                for name in self.graph.heavy(branch):
                    c = choices.get(name)
                    if c is None:
                        continue
                    ch = _ref_choice(c)
                    node = node_cost(hw, part_layer(self.graph.layer[name],
                                                    ch.lm),
                                     ch.dl_in, ch.dl_out, dt)
                    shape, probs = self.layer_problems(hw, name, c)
                    m = rnoc.mesh(*shape)
                    c_lat = c_en = 0.0
                    for sets, chunk in probs:
                        cyc = schedules.get(sched_key(
                            shape[0], shape[1], sets, chunk, link_bw, freq,
                            pj))
                        small = len(sets) == 1 and len(sets[0]) <= 7
                        if cyc is None and small:
                            cyc = rnoc.exhaustive(m, sets, chunk)[1]
                        elif cyc is None and fallback:
                            cyc = rnoc.start_schedule(m, sets, chunk)
                        if (cyc is None or len(cyc) != len(sets)
                                or any(sorted(a) != sorted(s)
                                       for a, s in zip(cyc, sets))):
                            if numbers is not None:
                                numbers["sched_rel"] = math.inf
                            c_lat = c_en = math.inf
                            continue
                        load, lat, en = rnoc.cost(m, cyc,
                                                  [chunk] * len(sets),
                                                  link_bw, freq, pj, dt)
                        if numbers is not None:
                            if small:
                                opt = rnoc.exhaustive(m, sets, chunk)[0]
                                ex = rel(load, opt)
                            else:
                                bound = max_load_of(m, rnoc.start_schedule(
                                    m, sets, chunk), chunk)
                                ex = load / bound - 1.0
                            numbers["sched_rel"] = max(
                                numbers["sched_rel"], ex)
                        c_lat += lat
                        c_en += en
                    lat = node.latency_s + c_lat
                    energy = node.energy_pj * (ch.region.h_shape
                                               * ch.region.w_shape) + c_en
                    layers[name] = (lat, c_lat, energy, c_en)
                    ri = (ch.region.h_pos, ch.region.w_pos)
                    region_lat[ri] = region_lat.get(ri, 0.0) + lat
                    total_en += energy
            total_lat += max(region_lat.values()) if region_lat else 0.0
        return {"layers": layers, "latency_s": total_lat,
                "energy_pj": total_en}

    def cost(self, lat: float, energy_pj: float) -> float:
        return ((energy_pj * 1e-12) ** self.alpha) * (lat ** self.beta) \
            * self.gamma


def compare(ref: Reference, observed: list[dict], areas: list,
            schedules: dict) -> dict:
    """The numbers compared, over ``observed`` points and all ``areas``.

    ``observed`` holds, per sampled design point, ``values`` (the Table-II
    tuple), ``cost`` (the observation's Eq. 1 cost) and ``mapping`` (the
    neutral mapping + report of :func:`capture.neutral_mapping`, or None
    where the program accounted no mapping for the point).
    ``areas`` holds ``(values, area_mm2)`` for every observation.
    """
    n = {k: 0.0 for k in NUMBERS}
    n["choices_differing"] = 0
    if areas:
        want = area_mm2([v for v, _ in areas], ref.constants)
        n["area_rel"] = max(rel(float(a), float(w))
                            for (_, a), w in zip(areas, want))
    for ob in observed:
        values, got = ob["values"], ob["mapping"]
        want = ref.mapping(values)
        if got is None:    # an observation no recorded mapping accounts for
            n["mapping_rel"] = n["result_rel"] = math.inf
            continue
        for name, c in want["choices"].items():
            g = got["choices"].get(name)
            if g is None:
                n["mapping_rel"] = math.inf
                continue
            n["choices_differing"] += _choice_key(g) != _choice_key(c)
            n["mapping_rel"] = max(n["mapping_rel"],
                                   rel(g["perf_s"], c["perf_s"]))
        if set(got["choices"]) - set(want["choices"]):
            n["mapping_rel"] = math.inf
        n["choices_differing"] += sum(got["sm"].get(i) != s
                                      for i, s in want["sm"].items())
        n["mapping_rel"] = max(n["mapping_rel"], rel(got["est_latency_s"],
                                                     want["est_latency_s"]))
        ev = ref.evaluate(values, got["choices"], schedules, n)
        for name, (lat, c_lat, energy, c_en) in ev["layers"].items():
            g = got["layers"].get(name)
            if g is None:
                n["sched_rel"] = math.inf
                continue
            n["sched_rel"] = max(n["sched_rel"], rel(g[1], c_lat),
                                 rel(g[3], c_en))
            n["result_rel"] = max(n["result_rel"], rel(g[0], lat),
                                  rel(g[2], energy))
        n["result_rel"] = max(
            n["result_rel"], rel(got["latency_s"], ev["latency_s"]),
            rel(got["energy_pj"], ev["energy_pj"]),
            rel(ob["cost"], ref.cost(ev["latency_s"], ev["energy_pj"])))
    return n


def search_quality(ref: Reference, observed: list[dict],
                   schedules: dict) -> dict:
    """How the program's schedules compare with a plain run of the search.

    Over every searched problem (not one set of seven or fewer nodes) of
    the sampled mappings: how many the program's search took below the best
    deterministic start, how many a plain copy of the search
    (``reference.noc.local_search``) took lower than the program did, and
    the largest ratio of the program's hottest link to the copy's.  A
    reading, not a limit: ``correct`` does not judge it (PERF.md).
    """
    out = {"problems": 0, "below_start": 0, "copy_lower": 0,
           "worst_ratio": 1.0}
    for ob in observed:
        if ob["mapping"] is None:
            continue
        hw = ref.hw(ob["values"])
        scal = (hw.link_bw_bytes, hw.c("freq_hz"),
                hw.c("noc_energy_pj_per_bit_hop"))
        for name, c in ob["mapping"]["choices"].items():
            shape, probs = ref.layer_problems(hw, name, c)
            m = rnoc.mesh(*shape)
            for sets, chunk in probs:
                if len(sets) == 1 and len(sets[0]) <= 7:
                    continue
                cyc = schedules.get(sched_key(*shape, sets, chunk, *scal))
                if cyc is None:
                    continue
                got = max_load_of(m, cyc, chunk)
                start = max_load_of(m, rnoc.start_schedule(m, sets, chunk),
                                    chunk)
                copy = rnoc.local_search(m, sets, chunk)
                out["problems"] += 1
                out["below_start"] += got < start
                out["copy_lower"] += copy < got
                if copy > 0:
                    out["worst_ratio"] = max(out["worst_ratio"], got / copy)
    return out


def control_observed(ref_low: Reference, sample: list[dict],
                     schedules: dict) -> tuple[list[dict], list]:
    """The lower-precision reference put in the program's place."""
    out = []
    for ob in sample:
        m = ref_low.mapping(ob["values"])
        ev = ref_low.evaluate(ob["values"], m["choices"], schedules,
                              fallback=True)
        m.update(ev)
        out.append({"values": ob["values"], "mapping": m,
                    "cost": ref_low.cost(ev["latency_s"], ev["energy_pj"])})
    return out


def control_areas(config: dict, areas: list, dt) -> list:
    got = area_mm2([v for v, _ in areas], config["constants"], dt)
    return [(v, float(a)) for (v, _), a in zip(areas, got)]


def judge(numbers: dict, limits: dict) -> tuple[bool, list[str]]:
    """``correct`` and one line per number: its value beside its limit."""
    ok, lines = True, []
    for k in NUMBERS:
        v, lim = numbers[k], limits[k]["limit"]
        good = v <= lim
        ok &= good
        lines.append(f"{k} {v!r} limit {lim!r} {'ok' if good else 'FAIL'}")
    return ok, lines
