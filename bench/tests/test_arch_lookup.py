"""A configuration's architecture is found by its ``model_type``: a new one
enters as files only, and the granite files read as they did."""
import os
import sys
import time
import types

import jax
import pytest

from bench import run, spec
from bench.archs import llama
from bench.reference import llama as ref_llama
from bench.tests import small

ARCH_FNS = ("program_config", "weight_layout", "linear_work")
REF_FNS = ("hidden", "head_stats")


def _recording(name: str, wrapped, fns, calls: set):
    mod = types.ModuleType(name)
    for fn in fns:
        def call(*a, _fn=fn, **k):
            calls.add(_fn)
            return getattr(wrapped, _fn)(*a, **k)
        setattr(mod, fn, call)
    return mod


def test_a_new_model_type_runs_through_its_own_modules(monkeypatch):
    calls = set()
    monkeypatch.setitem(sys.modules, "bench.archs.toyarch",
                        _recording("bench.archs.toyarch", llama, ARCH_FNS, calls))
    monkeypatch.setitem(
        sys.modules, "bench.reference.toyarch",
        _recording("bench.reference.toyarch", ref_llama, REF_FNS, calls))
    cell = small.cell("wtab-batch")
    cell.config = dict(cell.config, model_type="toyarch")
    res = run.run_cell(cell, 2**34 + 7, 1.5, False, small.PEAKS, jax.devices()[:1],
                       time.perf_counter())
    assert res["correct"] is True, res["check"]
    assert calls == set(ARCH_FNS + REF_FNS)


@pytest.mark.parametrize("lookup,kind", [(spec.arch, "archs"),
                                         (spec.reference, "reference")])
def test_an_unknown_model_type_names_the_file_it_expected(lookup, kind):
    with pytest.raises(SystemExit, match=f"bench/{kind}/qwen2_moe.py"):
        lookup({"model_type": "qwen2_moe"})


@pytest.mark.parametrize("family", sorted(spec.families()))
def test_a_model_type_may_not_take_a_family_name(family):
    for lookup in (spec.arch, spec.reference):
        with pytest.raises(SystemExit, match="family"):
            lookup({"model_type": family})


@pytest.mark.parametrize("name", sorted(
    f[:-3] for f in os.listdir(os.path.join(spec.BENCH, "archs"))
    if f.endswith(".py") and f != "__init__.py"))
def test_every_architecture_has_a_reference(name):
    c = {"model_type": name}
    assert all(callable(getattr(spec.arch(c), fn)) for fn in ARCH_FNS)
    assert all(callable(getattr(spec.reference(c), fn)) for fn in REF_FNS)


@pytest.mark.parametrize("config", ["granite_8b-wtab", "granite_8b-tl1"])
def test_the_granite_files_read_as_through_llama(config):
    entry = {c["name"]: c for c in spec.load_json(
        os.path.join(spec.ROOT, "BENCHMARK.json"))["configs"]}[config]
    c = dict(spec.load_json(os.path.join(spec.ROOT, entry["file"])), name=config)
    a = spec.arch(c)
    assert a is llama and spec.reference(c) is ref_llama
    assert a.program_config(c) == llama.program_config(c)
    assert a.weight_layout(c) == llama.weight_layout(c)
    assert a.linear_work(c) == llama.linear_work(c)
    assert a.linear_work(c) == {"linears": 4 * 218_103_808, "head": 49152 * 4096}


def test_the_harness_names_no_architecture():
    """Outside the architectures' and references' own modules and the
    tests, no file of the harness names one: it reaches them by
    ``model_type`` through ``spec``."""
    for d, _, files in os.walk(spec.BENCH):
        if os.path.relpath(d, spec.BENCH).split(os.sep)[0] in (
                "archs", "reference", "tests"):
            continue
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    assert "llama" not in fh.read(), os.path.join(d, f)
