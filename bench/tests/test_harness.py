"""The harness end to end on the CPU, at a tiny size: its look for a chip,
cells and metrics added as files, and faults that ``correct`` must catch."""

import json
import os
import shutil
from unittest import mock

import numpy as np
import pytest

from bench import control, gen, run
from repro.engine import sharded as sharded_engine
from repro.runtime import MultiTenantRuntime
from repro.serving.service import MultiTenantSSSJService

from .tiny import cpu, on_devices, tiny_cell

SEED = 2**31 + 5


@pytest.mark.parametrize("workload", ["dedup-d768.iso-sat",
                                      "dedup-d768x4.iso-sat"])
def test_no_tpu_exits_nonzero_without_a_result(workload, capsys):
    rc = run.main(["--workload", workload, "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_added_files_add_a_cell_and_a_metric(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(run.ROOT, "bench"),
                    os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: open(os.path.join(root, p), "rb").read()
              for p in ("bench/run.py", "bench/gen.py", "bench/client.py")}
    cfg = json.load(open(os.path.join(root, "bench/configs/dedup-d768.json")))
    cfg["name"], cfg["thetas"] = "tiny", [0.8, 0.82, 0.84, 0.86, 0.88, 0.9]
    # a new traffic kind as data alone: skewed tenant rates, an open loop
    mix = json.load(open(os.path.join(root, "bench/traffic/iso-rate.json")))
    mix.update(tenant_zipf=1.1, request_items=[1, 3])
    for path, obj in (("bench/configs/tiny.json", cfg),
                      ("bench/traffic/tiny-sat.json", mix),
                      ("bench/limits/tiny.json",
                       json.load(open(os.path.join(
                           root, "bench/limits/dedup-d768.json"))))):
        json.dump(obj, open(os.path.join(root, path), "w"))
    with open(os.path.join(root, "bench/metrics/spans_seen.py"), "w") as f:
        f.write("def read(r):\n"
                "    return r.delta('runtime/spans_dispatched')\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.tiny-sat", "config": "tiny",
                               "traffic": "tiny-sat", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "spans_seen", "unit": "spans",
                               "better": "higher",
                               "source": "program_counter", "layer": "t",
                               "moves": "items_per_s",
                               "workloads": ["tiny.tiny-sat"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = tiny_cell(run.load_cell, "tiny.tiny-sat", root)
    assert len(cell["cfg"]["thetas"]) == 6
    share = np.bincount(gen.make_plan(cell["cfg"], cell["mix"], SEED,
                                      6000).tenant, minlength=6) / 6000
    assert share[0] > 2 * share[2] > 4 * share[5] > 0
    result, _ = run.run(cell, SEED, 1.0, True, chips=cpu)
    assert result["correct"], result["checks"]
    assert result["metrics"]["spans_seen"]["value"] > 0
    assert "host_prep_ms" not in result["metrics"]
    for p, data in before.items():
        assert open(os.path.join(root, p), "rb").read() == data


def _alter(kind):
    real = MultiTenantSSSJService.flush

    def flush(self, final=False):
        out = real(self, final)
        if kind == "score":
            return {t: [(a, b, s + 1e-3) for a, b, s in p]
                    for t, p in out.items()}
        return {t: p[::2] for t, p in out.items()}     # half the answers
    return flush


_real_merge = sharded_engine.merge_candidates


def _shard0_only(cands, max_pairs):
    """The level-3 merge given only shard 0's candidates: the others'
    never reach it."""
    keep = (np.arange(cands.kept.shape[0]) == 0).astype(np.int32)
    return _real_merge(cands._replace(kept=cands.kept * keep,
                                      emitted=cands.emitted * keep),
                       max_pairs=max_pairs)


def check_run(workload, fault=None):
    """A tiny run of ``workload``: correct when sound; with the exchange
    between chips left out, not correct, and by missing pairs alone."""
    cell = tiny_cell(run.load_cell, workload, anchored_share=0.2)
    with mock.patch.object(sharded_engine, "merge_candidates",
                           _shard0_only if fault else _real_merge):
        result, checks = run.run(cell, SEED, 1.0, False, chips=cpu)
    assert result["device"]["count"] == cell["workload"]["chips"]
    assert list(result)[-1] == "checks"
    if fault:
        assert not result["correct"]
        assert {k for k, c in checks.items() if not c["ok"]} == \
            {"missing"}, checks
        return
    assert result["correct"], checks
    assert all(c["ok"] for c in checks.values()), checks
    assert checks["ref_pairs"]["value"] > 0


@pytest.fixture(scope="module")
def sharded():
    """The runs on a mesh, made together on four devices."""
    return on_devices(4, check_run, [("dedup-d768x4.iso-sat", None),
                                     ("dedup-d768x4.iso-sat", "exchange")])


@pytest.mark.parametrize("workload", ["dedup-d768.iso-sat",
                                      "trend-d384.burst-rate",
                                      "dedup-d768x4.iso-sat"])
def test_sound_tiny_run_is_correct(workload, request):
    if run.load_cell(workload)["workload"]["chips"] == 1:
        check_run(workload)
        return
    failure = request.getfixturevalue("sharded")[(workload, None)]
    assert failure is None, failure


def test_exchange_left_out_is_not_correct(sharded):
    failure = sharded[("dedup-d768x4.iso-sat", "exchange")]
    assert failure is None, failure


@pytest.mark.parametrize("kind", ["score", "drop"])
def test_altered_answers_are_not_correct(kind, monkeypatch):
    monkeypatch.setattr(MultiTenantSSSJService, "flush", _alter(kind))
    result, checks = run.run(tiny_cell(run.load_cell, "trend-d384.burst-sat"),
                             SEED, 1.0, False, chips=cpu)
    assert not result["correct"]
    failed = {k for k, c in checks.items() if not c["ok"]}
    assert failed == ({"score_gap"} if kind == "score" else {"missing"})


def _double_one_pair(self, final=False):
    out = _real_flush(self, final)
    for t, p in out.items():
        if p:
            return {**out, t: p + p[:1]}
    return out


_real_flush = MultiTenantSSSJService.flush
_real_rt_flush = MultiTenantRuntime.flush


def _ignore_final(self, final=False):
    """Deadline and end-of-stream flushes pad nothing: rows stay queued."""
    return _real_rt_flush(self, False)


def _hold_back(self, final=False):
    """Every flush but the last dispatches one micro-batch at most."""
    mb = self.cfg.micro_batch
    if final or len(self.router) < 2 * mb:
        return _real_rt_flush(self, final)
    self._dispatch(*self.router.take(mb))
    return mb


@pytest.mark.parametrize("fault,cls,patch,workload,fails", [
    ("doubled pair", MultiTenantSSSJService, _double_one_pair,
     "dedup-d768.iso-sat", {"duplicate"}),
    ("rows left queued", MultiTenantRuntime, _ignore_final,
     "dedup-d768.iso-rate", {"unreturned", "miscounted"}),
    ("rows counted before dispatch", MultiTenantRuntime, _hold_back,
     "dedup-d768.iso-sat", {"miscounted"}),
])
def test_service_faults_are_not_correct(fault, cls, patch, workload, fails,
                                        monkeypatch):
    monkeypatch.setattr(cls, "flush", patch)
    cell = tiny_cell(run.load_cell, workload, anchored_share=0.2)
    result, checks = run.run(cell, SEED, 1.0, False, chips=cpu)
    assert not result["correct"], fault
    assert {k for k, c in checks.items() if not c["ok"]} == fails, checks


@pytest.mark.parametrize("workload", ["dedup-d768.iso-sat",
                                      "trend-d384.burst-sat",
                                      "dedup-d768x4.iso-sat"])
def test_bf16_control_is_not_correct(workload):
    cell = tiny_cell(run.load_cell, workload)
    out = control.control(cell, SEED, rows=256)
    assert not out["correct"], out
    assert "score_gap" in out["fails"]
    assert np.isfinite(out["numbers"]["score_gap"])
