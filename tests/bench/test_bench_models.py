"""A model kind is one file under ``bench/models/``, found by the name a
configuration's ``layout.kind`` gives it: a new kind reaches the reference
and the FLOP count with no other file changed, and a kind with no file is
an error that names the file looked for."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import catalog, flops, reference

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
FUNCTIONS = ("arch", "init", "hidden", "matmul_params", "forward_flops")

TOY = '''"""A toy kind: each layer adds a square matmul of its normed input."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.models._common import _dense, _lm_params, _mm, _rmsnorm


def arch(lay):
    return {"toy_width": lay["d_model"]}


def init(a, key):
    ks = jax.random.split(key, 3)
    blocks = jax.vmap(lambda k: {
        "norm": {"w": jnp.zeros((a["d"],), jnp.float32)},
        "w": _dense(k, (a["d"], a["toy_width"]))})(
            jax.random.split(ks[0], a["layers"]))
    return _lm_params(a, [blocks], ks[1], ks[2])


def hidden(a, params, tokens, pr):
    h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def layer(h, p):
        return h + _mm(_rmsnorm(h, p["norm"]["w"], a["eps"]), p["w"], pr), None

    h, _ = jax.lax.scan(layer, h, params["blocks"][0])
    return _rmsnorm(h, params["final_norm"]["w"], a["eps"])


def matmul_params(lay):
    d = lay["d_model"]
    return lay["num_layers"] * d * d + d * lay["vocab_size"]


def forward_flops(lay, lens):
    return float(np.sum(lens)) * 2 * matmul_params(lay)
'''

DRIVE = '''
import json
import jax
import numpy as np
from bench import flops, reference

config = {"layout": {"kind": "toy", "num_layers": 2, "d_model": 32,
                     "vocab_size": 100, "padded_vocab": 128,
                     "norm_eps": 1e-6, "tie_embeddings": False}}
a = reference.arch_of(config)
params = reference.init_params(a, 2**31 + 5)
tokens = np.random.default_rng(0).integers(3, 100, (2, 24)).astype(np.int32)
lp, ent = reference.token_stats(a, params, tokens)
print(json.dumps({
    "file": reference.__file__, "arch": a,
    "shapes": {k: list(v.shape) for k, v in
               zip(reference.leaf_names(params),
                   jax.tree.leaves(params))},
    "lp_shape": list(lp.shape), "lp_max": float(lp[:, 1:].max()),
    "lp_finite": bool(np.isfinite(lp).all() and np.isfinite(ent).all()),
    "lp_distinct": int(len(np.unique(lp[:, 1:]))),
    "flops": flops.iteration_flops(config["layout"], [3], [2])}))
'''


def test_a_new_kind_is_one_file(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(CHECKOUT / "bench", root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    (root / "models" / "toy.py").write_text(TOY)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", DRIVE], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert pathlib.Path(got["file"]).resolve().is_relative_to(
        tmp_path.resolve())
    assert got["arch"]["kind"] == "toy" and got["arch"]["toy_width"] == 32
    assert got["shapes"] == {
        "['blocks'][0]['norm']['w']": [2, 32],
        "['blocks'][0]['w']": [2, 32, 32], "['embed']": [128, 32],
        "['final_norm']['w']": [32], "['lm_head']": [32, 128]}
    assert got["lp_shape"] == [2, 24] and got["lp_finite"]
    assert got["lp_max"] < 0 and got["lp_distinct"] > 40
    n = 2 * 32 * 32 + 32 * 100  # generation 4 tokens, forwards 5 and 3 x 5
    assert got["flops"] == (4 + 5 + 15) * 2 * n
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} changed"


UNKNOWN = {"kind": "no-such-kind", "num_layers": 1, "d_model": 8,
           "vocab_size": 16, "padded_vocab": 16, "norm_eps": 1e-6,
           "tie_embeddings": True}


@pytest.mark.parametrize("entry", [
    lambda lay: catalog.model(lay["kind"]),
    lambda lay: reference.arch_of({"layout": lay}),
    lambda lay: flops.matmul_params(lay),
    lambda lay: flops.iteration_flops(lay, [3], [2]),
], ids=["catalog.model", "arch_of", "matmul_params", "iteration_flops"])
def test_an_unknown_kind_names_the_file_it_looked_for(entry):
    want = str(catalog.ROOT / "models" / "no-such-kind.py")
    with pytest.raises(LookupError, match="no model kind") as err:
        entry(UNKNOWN)
    assert want in str(err.value)


def test_a_helper_module_is_no_kind():
    with pytest.raises(LookupError, match="_common.py"):
        catalog.model("_common")


def test_every_configuration_names_a_kind_with_the_five_functions():
    kinds = {p.stem for p in (catalog.ROOT / "models").glob("*.py")
             if not p.name.startswith("_")}
    configs = [catalog.load_config(p.stem)
               for p in (catalog.ROOT / "configs").glob("*.json")]
    assert {c["layout"]["kind"] for c in configs} <= kinds
    for kind in kinds:
        model = catalog.model(kind)
        assert all(callable(getattr(model, f, None)) for f in FUNCTIONS), kind


def test_a_kind_is_loaded_once():
    assert catalog.model("dense") is catalog.model("dense")
    assert catalog.model("dense") is not catalog.model("ssm")
