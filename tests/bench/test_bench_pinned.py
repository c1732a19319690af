"""The reference and the FLOP count of the small dense and SSD cells, pinned
bit for bit: the values were recorded on the CPU before the models moved
out of ``bench/reference.py`` and ``bench/flops.py`` into
``bench/models/<kind>.py``, and a refactor of either side must keep them.

The reference follows three synthetic GRPO steps (token ids, masks and
answers drawn from a fixed NumPy seed) from the weights of seed 7. Arrays
are pinned by the SHA-256 of their float32 bytes, scalars by ``float.hex``.
"""
import hashlib

import numpy as np
import pytest

from bench import catalog, flops, reference
from conftest import TINY, tiny_cell

SEED = 7
ROWS, WIDTH, STEPS = 8, 32, 3
PROMPTS, RESPONSES = [3, 17, 256, 1], [2, 0, 1024, 64]


def _steps(vocab: int):
    rng = np.random.default_rng(20240601)
    out = []
    for _ in range(STEPS):
        tokens = np.zeros((ROWS, WIDTH), np.int32)
        mask = np.zeros((ROWS, WIDTH), np.int32)
        for r in range(ROWS):
            p = int(rng.integers(4, 17))
            n = int(rng.integers(1, WIDTH - p + 1))
            tokens[r, :p + n] = rng.integers(3, vocab, p + n)
            mask[r, p:p + n] = 1
        out.append({"tokens": tokens, "mask": mask,
                    "answers": rng.integers(0, 100, ROWS)})
    return out


def _digest(arrays) -> str:
    return hashlib.sha256(b"".join(
        np.ascontiguousarray(a, np.float32).tobytes() for a in arrays)
    ).hexdigest()


def _hex(values):
    return [float(v).hex() for v in values]


def _flops(config: dict) -> str:
    return float(flops.iteration_flops(config["layout"], PROMPTS,
                                       RESPONSES)).hex()


def readings(kind: str) -> dict:
    """What is pinned of one small cell, and the FLOP count of the full
    configuration it was cut from."""
    cell = tiny_cell(kind)
    arch = reference.arch_of(cell["config_spec"])
    rl = dict(cell["rl"], group_size=cell["traffic_spec"]["group_size"])
    out = reference.follow(arch, SEED, _steps(arch["vocab"]), rl)
    return {"old_lp": _digest(out["old_lp"]), "ref_lp": _digest(out["ref_lp"]),
            "loss": _hex(out["loss"]), "grad_norms": _hex(out["grad_norms"]),
            "delta_norms": _hex(out["delta_norms"]),
            "iteration_flops": _flops(cell["config_spec"]),
            "config_flops": _flops(catalog.load_config(TINY[kind][0]))}


PINNED = {
    "dense": {
        "old_lp": ("44b93c5f178d30becf96988b9def7f7d"
                  "5f2383370842f19a1a964c8a6c84233b"),
        "ref_lp": ("20648585e4e978348f804ed4647cbdff"
                  "3c2f4207b1af4803dc239ed28d999a01"),
        "loss": [
            "-0x1.77466f8000000p-8", "-0x1.769ccb7000000p-8",
            "-0x1.762c63b000000p-8",
        ],
        "grad_norms": [
            "0x1.dacf240000000p-14", "0x1.caff4c0000000p-13",
            "0x1.ca511c0000000p-14", "0x1.e247500000000p-13",
            "0x1.08772a0000000p-13", "0x1.07bc7a0000000p-13",
            "0x1.74dad00000000p-13", "0x1.0a72560000000p-15",
            "0x1.479ce80000000p-16", "0x1.8ed4be0000000p-10",
            "0x1.0dae420000000p-13", "0x1.4acd2c0000000p-13",
        ],
        "delta_norms": [
            "0x1.eb332e0000000p-4", "0x1.5e2e640000000p-3",
            "0x1.5aef960000000p-3", "0x1.e614ee0000000p-4",
            "0x1.e92c720000000p-3", "0x1.ec548a0000000p-3",
            "0x1.ec03b20000000p-3", "0x1.3e3b9a0000000p-6",
            "0x1.50ace80000000p-6", "0x1.b4e4540000000p-3",
            "0x1.7f86c80000000p-6", "0x1.80f7cc0000000p-2",
        ],
        "iteration_flops": "0x1.a7095c0000000p+31",
        "config_flops": "0x1.afbf4f4400000p+42",
    },
    "ssm": {
        "old_lp": ("11ccb76e3edde12741a774e51b1b428c"
                  "35cf9aa91944d5b21d1ad41bc48578b8"),
        "ref_lp": ("745888d0e665221e274d9bb70e3f3896"
                  "f5ae6cbb5d9d8925afdff4a3917538ab"),
        "loss": [
            "-0x1.966dad4000000p-8", "-0x1.96751fe000000p-8",
            "-0x1.967fafa000000p-8",
        ],
        "grad_norms": [
            "0x1.804d860000000p-22", "0x1.9383f60000000p-30",
            "0x1.49ea000000000p-23", "0x1.a2fa460000000p-25",
            "0x1.fe1e5a0000000p-21", "0x1.d1fb920000000p-29",
            "0x1.c16ea20000000p-23", "0x1.0f925c0000000p-24",
            "0x1.7694480000000p-24", "0x1.c6792a0000000p-25",
            "0x1.6891680000000p-19", "0x1.021a200000000p-19",
            "0x1.ec60a60000000p-20", "0x1.2f24860000000p-16",
            "0x1.bfa3e00000000p-19",
        ],
        "delta_norms": [
            "0x1.fbe0680000000p-7", "0x1.a4c42e0000000p-11",
            "0x1.726e340000000p-8", "0x1.76e1460000000p-8",
            "0x1.3b1fac0000000p-5", "0x1.3731980000000p-11",
            "0x1.14231a0000000p-6", "0x1.4f47100000000p-7",
            "0x1.b7d9a20000000p-7", "0x1.29cffc0000000p-7",
            "0x1.4407300000000p-3", "0x1.1abde80000000p-3",
            "0x1.193d3e0000000p-3", "0x1.510c280000000p-2",
            "0x1.7b3b140000000p-6",
        ],
        "iteration_flops": "0x1.37e9000000000p+30",
        "config_flops": "0x1.f297a40000000p+42",
    },
}


@pytest.fixture(scope="module", params=["dense", "ssm"])
def pinned(request):
    return readings(request.param), PINNED[request.param]


@pytest.mark.parametrize("what", ["old_lp", "ref_lp", "loss", "grad_norms",
                                  "delta_norms", "iteration_flops",
                                  "config_flops"])
def test_reference_and_flops_are_bitwise_the_recorded(pinned, what):
    got, want = pinned
    assert got[what] == want[what]
