"""Small stand-ins of the benchmark's configurations for CPU tests."""

import copy

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
