"""Builds a driver for a cell at its ``rehearse`` sizes, without the
harness's look for a chip."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import importlib          # noqa: E402

from perfbench import common    # noqa: E402


def tiny_env(cell_name, seed=1, config_override=None, cell_override=None):
    import jax
    cell = common.load_json(common.named_file("workloads", cell_name))
    config = common.load_json(common.named_file("configs", cell["config"]))
    cell = common.merged(cell, cell.get("rehearse"))
    config = common.merged(config, config.get("rehearse"))
    cell = common.merged(cell, cell_override)
    config = common.merged(config, config_override)
    return common.Env(cell_name, cell, config, seed, jax.devices()[:1],
                      rehearse=True, tracing=False, log=lambda m: None)


def tiny_driver(cell_name, **kw):
    env = tiny_env(cell_name, **kw)
    mod = importlib.import_module("perfbench.drivers." + env.cell["driver"])
    return mod.Driver(env)
