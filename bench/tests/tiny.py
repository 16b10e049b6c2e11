"""Small stand-ins of the benchmark's configurations for CPU tests, and a
way to run a check on more CPU devices than the test process has."""

import copy
import json
import os
import subprocess
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CFG = {
    "d": 32, "capacity": 1024, "micro_batch": 8, "span": 2, "tile_k": 64,
    "max_pairs": 512, "max_queue_per_tenant": 4096,
}


def tiny_cell(load_cell, workload, root=None, **mix):
    """``workload``'s cell from BENCHMARK.json, cut to a CPU-test size."""
    cell = load_cell(workload) if root is None else load_cell(workload, root)
    cell = copy.deepcopy(cell)
    cell["cfg"].update(TINY_CFG)
    cell["mix"]["sample_rows"] = 48
    if "pool_items_per_s" in cell["mix"]:
        cell["mix"]["pool_items_per_s"] = 160
    else:
        cell["mix"]["client"]["rate"] = 200.0
    if "story_sizes" in cell["mix"]:
        cell["mix"]["story_sizes"] = [2, 32]
    cell["mix"].update(mix)
    return cell


def cpu(chips):
    import jax

    return jax.devices()


def on_devices(n: int, check, cases) -> dict:
    """``check(*case)`` for each case of ``cases`` where ``n`` devices are
    seen: here when the process has them, else all in one child process
    that forces ``n`` CPU devices (the test process keeps the one it has).
    Returns each case's failure as text, or ``None`` where it held."""
    import jax

    if jax.device_count() >= n:
        out = {}
        for case in cases:
            try:
                check(*case)
                out[case] = None
            except Exception:
                out[case] = traceback.format_exc()
        return out
    code = (f"import json\n"
            f"from bench.tests.tiny import on_devices\n"
            f"from {check.__module__} import {check.__name__}\n"
            f"out = on_devices({n}, {check.__name__}, {cases!r})\n"
            f"print(json.dumps(list(out.values())))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT]))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n}")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=ROOT, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return dict(zip(cases, json.loads(r.stdout.splitlines()[-1])))
