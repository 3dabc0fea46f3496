"""The benchmark's files agree with each other and with the program's
configurations."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
import re

from chipbench import program
from chipbench import spec
import pytest

from repro.configs import get_arch

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj)_size|_dim$|"
                   r"_rank$|^d_(model|ff|state|inner)$|headdim|expand|"
                   r"per_tok")


def _diff(a, b):
    return {f.name for f in dataclasses.fields(a)
            if getattr(a, f.name) != getattr(b, f.name)}


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_states_its_cuts(entry):
    cfile = json.loads((REPO / entry["file"]).read_text())
    assert cfile["name"] == entry["name"]
    assert cfile["reduced"] == entry["reduced"]
    pub, run = cfile["published"], cfile["config"]
    assert set(pub) == set(run)
    assert {k for k in pub if pub[k] != run[k]} == set(entry["reduced"])
    assert not any(WIDTH.search(k) for k in entry["reduced"])
    # ``assumed`` fills in only what the source leaves out
    for k, v in cfile["assumed"].items():
        if isinstance(v, dict):
            assert not set(v) & set(pub.get(k, {}))
        else:
            assert k not in pub


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_program_config_differs_only_where_stated(entry):
    """Built from the published numbers (and the sizes ``assumed``), the
    program's configuration differs from ``get_arch`` only in keys that
    ``notes`` explains; built from the numbers as run, only where
    ``reduced`` changes them."""
    cfile = json.loads((REPO / entry["file"]).read_text())
    ref = spec.family_module("refs", cfile["family"])
    base = get_arch(cfile["base_arch"])
    published = spec.as_run(dict(cfile, config=cfile["published"]))
    pub = program.arch(cfile["base_arch"], ref.arch_fields(published))
    run = program.arch(cfile["base_arch"],
                       ref.arch_fields(spec.as_run(cfile)))
    assert _diff(pub, base) <= set(cfile["notes"])
    cut = dict(published)
    for k in cfile["reduced"]:
        cut[k] = cfile["config"][k]
    assert program.arch(cfile["base_arch"], ref.arch_fields(cut)) == run


def test_every_name_has_its_files():
    names = {c["name"] for c in BENCH["configs"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        assert w["config"] in names
        assert spec.load_traffic(REPO, w["traffic"])
    for m in BENCH["per_layer"]:
        assert callable(spec.metric_reader(REPO, m["name"]))
        assert set(m.get("workloads", cells)) <= cells
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert {m["moves"] for m in BENCH["per_layer"]} <= e2e
