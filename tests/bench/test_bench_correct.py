"""The comparison that decides ``correct``, on small cells on the CPU: the
plain reference starts from the same weights as the program, a sound run
comes out correct, and the control and every planted fault come out not
correct under the cells' own limits. The harness skips its look for a chip
here and drives the rest of a run."""
import time

import jax
import numpy as np
import pytest

from bench import harness, reference


def _run(cell, fault=None, seed=7):
    return harness.run("tiny", seed, 0.5, False, t0=time.perf_counter(),
                       require_tpu=False, cell=cell, fault=fault)


@pytest.mark.parametrize("kind", ["dense", "ssm"])
def test_reference_builds_the_programs_initial_weights(tiny, kind):
    import dataclasses

    from repro.configs import get_config
    from repro.models import get_model

    cell = tiny(kind)
    prog = cell["config_spec"]["program"]
    model = get_model(dataclasses.replace(get_config(prog["registry"]),
                                          **prog["overrides"]))
    seed = 2**31 + 3
    want = model.init(jax.random.split(jax.random.PRNGKey(seed), 3)[0])
    got = reference.init_params(reference.arch_of(cell["config_spec"]), seed)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    # the reference draws in one jitted call, the program op by op; XLA's
    # fusion may round a rare element to its neighbouring value
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        ulp = 2.0**-7 if a.dtype == jax.numpy.bfloat16 else 2.0**-22
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.all(np.abs(a - b) <= np.abs(b) * ulp)
        if a.size > 1000:
            assert np.mean(a != b) <= 1e-3


@pytest.mark.parametrize("kind", ["dense", "ssm"])
def test_sound_run_is_correct_and_its_control_is_not(tiny, kind):
    cell = tiny(kind)
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["metrics"]["tokens_per_s_per_chip"]["value"] > 0
    # the control: the reference one precision below, in the program's place
    sess = harness.Session(cell, 7)
    sess.setup()
    sess.release()
    arch = reference.arch_of(cell["config_spec"])
    rl = dict(cell["rl"], group_size=cell["traffic_spec"]["group_size"])
    steps = harness.reference_steps(sess.rec.steps)
    ref = reference.follow(arch, 7, steps, rl)
    ctrl = reference.follow(arch, 7, steps, rl, precision="fp8")
    nums = reference.compare(
        {"mask": [s["mask"] for s in steps], "old_lp": ctrl["old_lp"],
         "ref_lp": ctrl["ref_lp"], "loss": ctrl["loss"],
         "grad_norms": ctrl["grad_norms"],
         "delta_norms": ctrl["delta_norms"]}, ref)
    assert any(nums[k] > lim for k, lim in cell["limits"].items()), nums


class _Broken:
    """An engine with one call broken; every attribute is the engine's."""

    def __init__(self, engine, call):
        self._engine, self._call = engine, call

    def __getattr__(self, attr):
        return getattr(self._engine, attr)

    def __call__(self, *args, **kwargs):
        return self._call(self._engine, *args, **kwargs)


def _state_unchanged(engine, state, batch):
    _, metrics = engine(state, batch)
    return state, metrics


def _half_batch(engine, state, batch):
    n = batch["tokens"].shape[0] // 2
    return engine(state, {k: v[:n] for k, v in batch.items()})


def _token_altered(engine, params, prompts, key, **kw):
    res = engine(params, prompts, key, **kw)
    tokens = np.array(res.tokens)
    mask = np.asarray(res.response_mask)
    for r in range(len(tokens)):
        t = np.flatnonzero(mask[r])[0]
        tokens[r, t] = 3 + (int(tokens[r, t]) - 3 + 250) % 497
    return res._replace(tokens=jax.numpy.asarray(tokens))


@pytest.mark.parametrize("kind", ["dense", "ssm"])
@pytest.mark.parametrize("engine,call", [
    ("actor_step", _state_unchanged),
    ("actor_step", _half_batch),
    ("generate", _token_altered),
], ids=["state_unchanged", "half_batch", "token_altered"])
def test_planted_fault_comes_out_not_correct(tiny, kind, engine, call):
    def fault(pipe):
        engines = pipe.ctx.engines
        engines[engine] = _Broken(engines[engine], call)

    res = _run(tiny(kind), fault=fault)
    assert not res["correct"], res["checks"]
