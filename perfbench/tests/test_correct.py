"""``correct`` has to come out false when it should.

Two kinds of proof, both at sizes a test run can hold (the same readings
were taken on the chip at the cells' own sizes; PERF.md has them):

* the control — the plain reference put in the program's place and computed
  in the nearest precision below the configuration's: for bfloat16 training
  its products in 8-bit floating point (the usual fp8 recipe,
  ``reference/lowprec.py``) — fails one of the cell's numbers against the
  rehearsal limits, driven through ``run.run_cell``;
* a run driven through ``run.run_cell`` (everything after the harness's look
  for a chip) with the timed path broken underneath — a step that returns its
  state unchanged, half of every batch left out — reports ``correct: false``.

The rehearsal limits (``rehearse.limits`` of each cell file) were set the way
the chip's were, from tiny-size readings: above what sound runs give, below
what the control and the faults give.
"""
import contextlib

import numpy as np
import pytest

from helpers import tiny_driver, tiny_env

from perfbench import common, run


def _run(cell_name, seed=7, seconds=1.0):
    import jax
    env = tiny_env(cell_name, seed=seed)
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    entry = common.cell_entry(bench, cell_name)
    return run.run_cell(bench, entry, env.cell, env.config, seed, seconds,
                        False, jax.devices()[:1], None, True)


@contextlib.contextmanager
def patched(obj, name, new):
    old = getattr(obj, name)
    setattr(obj, name, new(old))
    try:
        yield
    finally:
        setattr(obj, name, old)


# -- sound runs pass --------------------------------------------------------
@pytest.mark.parametrize("cell", ["resnet50_fit_b256", "gpt2m_train_s1024"])
def test_sound_run_is_correct(cell):
    line = _run(cell)
    assert line["correct"] is True, line["compared"]
    assert line["rehearsal"] is True
    assert list(line)[-1] == "compared"


# -- the controls fail ------------------------------------------------------
@pytest.mark.parametrize("cell", ["resnet50_fit_b256", "gpt2m_train_s1024"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_training_control_fails(cell, seed):
    """The reference with its products in 8-bit floating point, put in the
    program's place for a whole run, comes out not correct."""
    from perfbench import calibrate
    import importlib
    import jax
    env = tiny_env(cell, seed=seed)
    assert env.config["training"]["control"] == "float8_e4m3fn"
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    line = calibrate.through_run_cell(
        run, importlib.import_module("perfbench.drivers."
                                     + env.cell["driver"]),
        "control", bench, common.cell_entry(bench, cell), env.cell,
        env.config, seed, 0.5, jax.devices()[:1], True)
    assert line["correct"] is False, line["compared"]


# -- planted faults fail, through the harness --------------------------------
def _unchanged_trainer_step(real):
    import jax
    import jax.numpy as jnp

    def step(self, params, opt_state, aux, batch, rng=None):
        kept = jax.tree_util.tree_map(jnp.copy, (params, opt_state, aux))
        _p, _o, _a, outs = real(self, params, opt_state, aux, batch, rng)
        return kept[0], kept[1], kept[2], outs
    return step


def _half_batch_trainer_step(real):
    import jax.numpy as jnp

    def step(self, params, opt_state, aux, batch, rng=None):
        def first_half_twice(a):
            half = a.shape[0] // 2
            return jnp.concatenate([a[:half], a[:half]], axis=0)
        batch = {k: first_half_twice(v) for k, v in batch.items()}
        return real(self, params, opt_state, aux, batch, rng)
    return step


@pytest.mark.parametrize("fault", [_unchanged_trainer_step,
                                   _half_batch_trainer_step])
def test_trainer_faults_are_caught(fault):
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    with patched(ShardedTrainer, "step", fault):
        line = _run("gpt2m_train_s1024")
    assert line["correct"] is False, line["compared"]


def _unchanged_fused_step(real):
    import jax
    import jax.numpy as jnp

    def fused_step(self, optimizer, states, num_update, **kw):
        names = [n for n in self._arg_names
                 if self._grad_req.get(n, "null") != "null"]
        kept_w = {n: jnp.copy(self.arg_dict[n].data) for n in names}
        kept_s = jax.tree_util.tree_map(jnp.copy, states)
        real(self, optimizer, states, num_update, **kw)
        for n in names:
            self.arg_dict[n]._set_data(kept_w[n])
        return kept_s
    return fused_step


def _half_batch_load(real):
    import mxnet_tpu as mx

    def load_data_batch(self, data_batch):
        def first_half_twice(nd):
            a = nd.asnumpy()
            half = a.shape[0] // 2
            return mx.nd.array(np.concatenate([a[:half], a[:half]]))
        real(self, mx.io.DataBatch(
            data=[first_half_twice(d) for d in data_batch.data],
            label=[first_half_twice(d) for d in data_batch.label],
            pad=0, index=None))
    return load_data_batch


def test_fit_unchanged_state_is_caught():
    from mxnet_tpu.executor import Executor
    with patched(Executor, "fused_step", _unchanged_fused_step):
        line = _run("resnet50_fit_b256")
    assert line["correct"] is False, line["compared"]


def test_fit_half_batch_is_caught():
    from mxnet_tpu.module.executor_group import DataParallelExecutorGroup
    with patched(DataParallelExecutorGroup, "load_data_batch",
                 _half_batch_load):
        line = _run("resnet50_fit_b256")
    assert line["correct"] is False, line["compared"]


def test_weights_left_unmoved_fail_the_matrices_rows():
    """A fault on a minority of the leaves — the convolution and classifier
    weights never updated — leaves the median leaf where it was and is the
    ``_matrices`` rows' to catch."""
    from perfbench.drivers.train_fit import compare_training
    limits = common.load_json(common.named_file(
        "workloads", "resnet50_fit_b256"))["limits"]
    rank = {"w%d" % i: 4 for i in range(54)}
    rank.update({"bn%d" % i: 1 for i in range(106)})
    ref = {"loss": [6.9, 6.9, 6.9], "logp": np.log(np.full((4, 10), 0.1))
           + np.arange(10) * 0.01, "rank": rank,
           "grad": {n: 1.0 for n in rank}, "delta": {n: 0.3 for n in rank}}
    prog = dict(ref, delta={n: (0.0 if r > 1 else 0.3)
                            for n, r in rank.items()})
    rows = {n: (v, lim) for n, v, lim in compare_training(prog, ref, limits)}
    assert rows["delta_norm_gap_median"][0] == 0.0
    value, limit = rows["delta_norm_gap_matrices"]
    assert value == 1.0 and value > limit
