"""Each configuration file's graph is the network its builder exports."""

import json

import pytest

import spec

BUILDERS = {"googlenet": "googlenet", "vgg16": "vgg16"}


def rows(g):
    out = []
    for l in g.layers:
        d = {"name": l.name, "kind": l.kind}
        d.update({k: getattr(l, k) for k in
                  ("B", "C", "H", "W", "K", "HK", "WK", "stride", "pad")})
        d["preds"] = list(g.preds(l.name))
        out.append(d)
    return out


@pytest.mark.parametrize("name", sorted(BUILDERS))
def test_graph_matches_builder(name):
    from repro.core import workloads
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    g = getattr(workloads, BUILDERS[name])(1)
    assert cfg["graph"]["name"] == g.name
    assert cfg["graph"]["layers"] == rows(g)
    built = spec.program_graph(cfg)
    assert [l for l in built.layers] == list(g.layers)


@pytest.mark.parametrize("name,heavy,layers", [("googlenet", 58, 81),
                                               ("vgg16", 16, 21)])
def test_published_network_shape(name, heavy, layers):
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    ls = cfg["graph"]["layers"]
    assert len(ls) == layers
    assert sum(l["kind"] in ("conv", "matmul") for l in ls) == heavy
    if name == "vgg16":     # 25088 x 4096 first fully connected layer
        fc0 = next(l for l in ls if l["name"] == "fc0")
        assert (fc0["C"], fc0["K"]) == (512 * 7 * 7, 4096)


@pytest.mark.parametrize("name", sorted(BUILDERS))
def test_design_space_is_the_programs(name):
    cfg = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    cons = spec.program_constraints(cfg)
    assert cons.area_budget_mm2 == 48.0
