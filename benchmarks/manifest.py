"""``BENCHMARK.json``: look-ups, and the checks that can be made before a run.

The driver refuses a malformed file before it runs anything; ``check`` makes
the same refusals here, so that a later PR that adds an entry sees them at
once (``python3 benchmarks/manifest.py``).
"""

from __future__ import annotations

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


class Malformed(ValueError):
    pass


def _need(cond, what: str) -> None:
    if not cond:
        raise Malformed(what)


def _line(s, what: str) -> None:
    _need(isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s
          and "\t" not in s, f"{what}: 1 to 200 characters on one line")


def check(m: dict) -> None:
    _need(set(m) == KEYS, f"keys must be exactly {sorted(KEYS)}")
    _need(isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51,
          "run_seconds: a whole number from 1 to 51")
    for word in m["command"]:
        _line(word, "command word")
    names = set()

    def name(n, what):
        _need(isinstance(n, str) and NAME.match(n), f"{what}: bad name {n!r}")

    def unique(n, what):
        _need((what, n) not in names, f"{what} {n!r} appears twice")
        names.add((what, n))

    for c in m["configs"]:
        _need(set(c) == {"name", "source", "file", "reduced", "why"},
              f"config {c.get('name')!r}: wrong keys")
        name(c["name"], "config")
        unique(c["name"], "config")
        unique(c["file"], "config file")
        _line(c["source"], "source")
        _line(c["why"], "why")
        _need(any(c["file"].startswith(p + "/") for p in m["paths"]),
              f"config file {c['file']!r} is not under paths")
        _need(len(c["reduced"]) <= 16, "reduced: at most 16 keys")
        for k in c["reduced"]:
            name(k, "reduced key")
    cells = {}
    for w in m["workloads"]:
        _need(set(w) == {"name", "config", "traffic", "chips", "why"},
              f"workload {w.get('name')!r}: wrong keys")
        for k in ("name", "config", "traffic"):
            name(w[k], f"workload {k}")
        unique(w["name"], "workload")
        unique((w["config"], w["traffic"]), "config and traffic")
        _need(w["chips"] in (1, 4), "chips: 1 or 4")
        _line(w["why"], "why")
        _need(("config", w["config"]) in names,
              f"workload {w['name']!r}: unknown config {w['config']!r}")
        cells[w["name"]] = w
    _need(sum(w["chips"] == 4 for w in m["workloads"])
          <= max(1, len(m["workloads"]) // 4),
          "more than a quarter of the cells ask for 4 chips")
    e2e = {}
    for kind in ("end_to_end", "per_layer"):
        for x in m[kind]:
            want = {"name", "unit", "better", "source"}
            want |= ({"bound"} if kind == "end_to_end"
                     else {"layer", "moves"})
            _need(want <= set(x) <= want | {"workloads"},
                  f"metric {x.get('name')!r}: wrong keys")
            name(x["name"], "metric")
            unique(x["name"], "metric")
            _need(isinstance(x["unit"], str) and UNIT.match(x["unit"]),
                  f"metric {x['name']!r}: bad unit {x['unit']!r}")
            _need(x["better"] in ("lower", "higher"), "better: lower | higher")
            _need(x["source"] in SOURCES, f"source: one of {SOURCES}")
            for wl in x.get("workloads", ()):
                _need(wl in cells, f"metric {x['name']!r}: unknown cell {wl!r}")
            if kind == "end_to_end":
                _need(x["source"] in ("host_clock", "device_trace"),
                      "an end-to-end metric is host_clock or device_trace")
                _need(0 < x["bound"] <= 0.1, "bound: over 0, at most 0.1")
                e2e[x["name"]] = x
            else:
                _line(x["layer"], "layer")
                _need(x["moves"] in e2e,
                      f"metric {x['name']!r} moves no end-to-end metric")
    _need("setup_s" in e2e, "setup_s has to be an end-to-end metric")
    for c in m["configs"]:
        _need(any(w["config"] == c["name"] for w in m["workloads"]),
              f"config {c['name']!r} is used by no cell")


def cell(m: dict, name: str) -> dict:
    for w in m["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(m: dict, name: str) -> dict:
    return next(c for c in m["configs"] if c["name"] == name)


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        check(json.load(f))
    print("BENCHMARK.json: ok", file=sys.stderr)
