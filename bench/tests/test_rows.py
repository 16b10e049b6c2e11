"""The row-representation seam: the dense path through it makes what the
harness made before it had one, bit for bit, and a representation is added
by files alone."""

import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from bench import run

from .tiny import TINY_CFG, cpu, tiny_cell

# sha256 prefixes of what the harness made before the seam, on this fixture
# (tiny_cell with anchored_share 0.2, a plan of capacity + 512 arrivals)
PARENT = {
    ("dedup-d768.iso-sat", 7): {
        "block": 1024, "plan": "a327c8563de8f9d5",
        "host_rows": "3348207255c5961e", "ring": "c50f5780e6321336",
        "ring_paths": "f6af2052477d65a8", "reference": "4a27edf05125202a",
        "n_ref": 4},
    ("dedup-d768.iso-sat", 2**31 + 5): {
        "block": 1024, "plan": "306f83ad6c69d8a6",
        "host_rows": "8363d915059ee539", "ring": "304982ee289b4a7b",
        "ring_paths": "f6af2052477d65a8", "reference": "5ff433e70e0fe5ee",
        "n_ref": 9},
    ("trend-d384.burst-sat", 7): {
        "block": 1024, "plan": "96eb1528f2b72cc4",
        "host_rows": "c86377676c1a4129", "ring": "07a147883aeb1b28",
        "ring_paths": "f6af2052477d65a8", "reference": "fe29665feb9775cc",
        "n_ref": 21},
    ("trend-d384.burst-sat", 2**31 + 5): {
        "block": 1024, "plan": "a0b4fe755e218021",
        "host_rows": "2d86d2696e0f7093", "ring": "9c4c083a6d97b135",
        "ring_paths": "f6af2052477d65a8", "reference": "add10e55a6ec5c48",
        "n_ref": 47},
}


def _h(*arrays) -> str:
    m = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        m.update(f"{a.dtype.str}{a.shape}".encode())
        m.update(a.tobytes())
    return m.hexdigest()[:16]


def _digests(name, seed) -> dict:
    import jax

    cell = tiny_cell(run.load_cell, name, anchored_share=0.2)
    data, serve = run.row_sides(cell)
    cfg, mix = cell["cfg"], cell["mix"]
    cap = cfg["capacity"]
    block = data.block_rows(cfg)
    plan = data.make_plan(cfg, mix, seed, cap + 512)
    out = {"block": block,
           "plan": _h(plan.tenant, plan.starts, plan.anchor, plan.key_row,
                      plan.key_anchor, np.float64(plan.noise),
                      np.int64(plan.d)),
           "host_rows": _h(data.host_rows(plan, cap - 64, cap + 192, block))}
    svc = serve.build_service(cfg)
    serve.install(svc, plan, cap - 48, block)
    leaves = jax.tree_util.tree_flatten_with_path(svc.runtime.state)[0]
    out["ring"] = _h(*[np.asarray(x) for _, x in leaves])
    out["ring_paths"] = _h(np.frombuffer("".join(
        jax.tree_util.keystr(p) for p, _ in leaves).encode(), np.uint8))
    sample = np.sort(np.random.default_rng([seed, 2]).choice(
        np.arange(cap, cap + 256), 48, replace=False))
    ref = data.reference_pairs(plan, cfg, sample, np.ones(cap + 256, bool),
                               block, 48)
    got = [(g, j, s) for g in sorted(ref) for j, s in sorted(ref[g].items())]
    out["reference"] = _h(*(np.asarray(x, t) for x, t in zip(
        zip(*got), (np.int64, np.int64, np.float64))))
    out["n_ref"] = len(got)
    return out


@pytest.mark.parametrize("name,seed", sorted(PARENT))
def test_dense_through_the_seam_is_the_parents(name, seed):
    assert _digests(name, seed) == PARENT[(name, seed)]


def test_unknown_representation_names_its_file():
    cell = tiny_cell(run.load_cell, "dedup-d768.iso-sat")
    cell["cfg"]["rows"] = "nosuch"
    with pytest.raises(SystemExit, match="bench/rows/nosuch.py"):
        run.run(cell, 1, 1.0, False, chips=cpu)


# A stand-in device path for the sparse stream: its rows densified at a
# small dims and served by the dense service.
DENSIFIED_ROWS = '''
import numpy as np

from bench.rows.sparse import block_rows, make_plan, reference_pairs
from bench.rows import sparse


def host_rows(plan, lo, hi, block):
    r = sparse.host_rows(plan, lo, hi, block)
    out = np.zeros((hi - lo, plan.dims + 1), np.float32)
    np.put_along_axis(out, r.ids, r.weights, axis=1)
    return out[:, :plan.dims]
'''

DENSIFIED_SERVE = '''
import numpy as np

from bench import client
from bench.prefill import build_service
from bench.rows import sparse


def submit_args(rows, a, b):
    return (rows[a:b],)


def install(svc, plan, n_rows, block):
    r = sparse.host_rows(plan, 0, n_rows, block)
    rows = np.zeros((n_rows, plan.dims + 1), np.float32)
    np.put_along_axis(rows, r.ids, r.weights, axis=1)
    pool = client.Pool.of(plan, rows[:, :plan.dims], 0, n_rows, submit_args)
    client.run_client(svc, pool, {"span": 1, "micro_batch": 256},
                      {"kind": "backlog", "spans_queued": 1}, np.inf)
    svc.flush(final=True)
'''


def _tree(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = open(p, "rb").read()
    return out


def test_added_files_add_a_representation(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(run.ROOT, "bench"),
                    os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = _tree(root)
    cfg = json.load(open(os.path.join(root, "bench/configs/trend-d384.json")))
    cfg.update(name="tweets-tiny", rows="densified", d=256, dims=256,
               nnz_mean=9.46, nnz_max=32, nnz_sigma=0.6, zipf_s=1.3,
               stop_words=4)
    mix = json.load(open(os.path.join(root,
                                      "bench/traffic/burst-sat.json")))
    mix.update(keep=0.83)
    files = {
        "bench/rows/densified.py": DENSIFIED_ROWS,
        "bench/serve/densified.py": DENSIFIED_SERVE,
        "bench/configs/tweets-tiny.json": json.dumps(cfg),
        "bench/traffic/tweets-sat.json": json.dumps(mix),
        "bench/limits/tweets-tiny.json": open(os.path.join(
            root, "bench/limits/trend-d384.json")).read(),
    }
    for path, text in files.items():
        with open(os.path.join(root, path), "w") as f:
            f.write(text)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "tweets-tiny", "source": "test",
                             "file": "bench/configs/tweets-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tweets-tiny.tweets-sat",
                               "config": "tweets-tiny",
                               "traffic": "tweets-sat", "chips": 1,
                               "why": "test"})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = run.load_cell("tweets-tiny.tweets-sat", root)
    cell["cfg"].update({k: v for k, v in TINY_CFG.items() if k != "d"})
    cell["mix"].update(sample_rows=48, pool_items_per_s=160,
                       story_sizes=[2, 32])
    data, serve = run.row_sides(cell)
    assert data.__file__.startswith(root) and serve.__file__.startswith(root)
    result, checks = run.run(cell, 2**31 + 7, 1.0, False, chips=cpu)
    assert result["correct"], checks
    assert checks["ref_pairs"]["value"] > 0
    after = _tree(root)
    assert {p: after[p] for p in before} == before
    assert set(after) - set(before) == set(files)
