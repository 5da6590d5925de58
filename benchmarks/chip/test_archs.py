"""Each architecture's weight layout, reference and counts live in a file
of their own, ``archs/<program_arch>.py``, found by name.

The zamba2 readings (weight layouts, a digest of the test-size weights,
the test-size reference logits, the full-size counts and the program's
``ArchConfig``) are compared with ``testdata/zamba2_golden.json``,
recorded by ``observe`` before the layout, reference and counts moved
out of the generic files: exactly, and bit for bit for the weights and
the CPU logits. A toy architecture in a directory of its own reaches
the weights, the reference and the counts with no edit to them.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q benchmarks/chip
"""
from __future__ import annotations

import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

GOLDEN = os.path.join(HERE, "testdata", "zamba2_golden.json")
FULL = os.path.join(HERE, "configs", "zamba2-2.7b.json")
TINY = os.path.join(HERE, "testdata", "zamba2-tiny.json")
SEED = 2**33 + 17


def _load(path):
    with open(path) as f:
        return json.load(f)


def _plain(x):
    """``x`` as JSON gives it back (tuples become lists)."""
    return json.loads(json.dumps(x))


def _digest(tree) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes."""
    import jax
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def observe() -> dict:
    """The zamba2 readings the golden file holds."""
    import jax
    import counts
    import harness
    import reference as R
    import weights as W
    full, tiny = _load(FULL), _load(TINY)
    out = {"layout": {}, "program_config": {}}
    for name, cfg in (("full", full), ("tiny", tiny)):
        out["layout"][name] = {"/".join(p): [list(s), dt, list(i)]
                               for p, (s, dt, i) in W.layout(cfg).items()}
        out["program_config"][name] = repr(harness.program_config(cfg))
    params = W.make_params(tiny, SEED)
    out["weights_sha256"] = _digest(params)
    tokens = np.random.default_rng(7).integers(
        0, tiny["vocab"], (2, 24), dtype=np.int32)
    logits = np.asarray(jax.jit(lambda p, t: R.logits(p, tiny, t))(
        params, tokens))
    out["tokens"] = tokens.tolist()
    out["logits_sha256"] = hashlib.sha256(
        logits.astype(np.float32).tobytes()).hexdigest()
    out["logits_head"] = logits[0, :2, :4].tolist()
    out["counts"] = {
        "decode": {f"{r},{kv}": list(counts.decode(full, r, kv))
                   for r, kv in ((8, 1152), (8, 4096))},
        "prefill": {str(n): list(counts.prefill(full, n))
                    for n in (128, 1024)},
        "train_flops_per_token_1024": counts.train_flops_per_token(full, 1024),
        "param_bytes": W.param_bytes(full),
    }
    return _plain(out)


@pytest.fixture(scope="module")
def golden_and_now():
    return _load(GOLDEN), observe()


@pytest.mark.parametrize("key", ["layout", "counts", "program_config",
                                 "tokens"])
def test_zamba2_readings_exactly_as_recorded(golden_and_now, key):
    golden, now = golden_and_now
    assert now[key] == golden[key]


@pytest.mark.parametrize("key", ["weights_sha256", "logits_sha256"])
def test_zamba2_bits_as_recorded(golden_and_now, key):
    golden, now = golden_and_now
    assert now[key] == golden[key], (now["logits_head"],
                                     golden["logits_head"])


TOY = '''
"""A toy architecture: an embedding read straight into the output head."""
import jax.numpy as jnp


def layout(cfg):
    d, v = cfg["d_model"], cfg["vocab"]
    return {("embed",): ((v, d), "bfloat16", ("normal", 0.02)),
            ("lm_head",): ((d, v), "bfloat16", ("normal", d ** -0.5))}


def hidden(params, cfg, tokens, quant=False):
    return params["embed"][tokens].astype(jnp.float32)


def decode(cfg, rows, kv_positions):
    return 3.0, 5.0


def prefill(cfg, length):
    return 7.0, 11.0


def train_flops_per_token(cfg, seq):
    return 13.0


def program_fields(cfg, base):
    return {}
'''


@pytest.fixture
def toy_archs(tmp_path, monkeypatch):
    import arch
    (tmp_path / "toy-arch.py").write_text(TOY)
    monkeypatch.setattr(arch, "ARCHS", str(tmp_path))
    return tmp_path


def test_new_architecture_is_one_file(toy_archs):
    import jax
    import jax.numpy as jnp
    import counts
    import reference as R
    import weights as W
    cfg = {"program_arch": "toy-arch", "d_model": 16, "vocab": 32}
    params = W.make_params(cfg, SEED)
    assert sorted(params) == ["embed", "lm_head"]
    assert params["embed"].shape == (32, 16)
    assert W.param_bytes(cfg) == 2 * 2 * 16 * 32
    tokens = jnp.array([[1, 2, 3]], jnp.int32)
    got = R.logits(params, cfg, tokens)
    with jax.default_matmul_precision("highest"):
        want = params["embed"][tokens].astype(jnp.float32) \
            @ params["lm_head"].astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert counts.decode(cfg, 8, 100) == (3.0, 5.0)
    assert counts.prefill(cfg, 64) == (7.0, 11.0)
    assert counts.train_flops_per_token(cfg, 64) == 13.0


def test_missing_architecture_names_its_path(toy_archs):
    import weights as W
    path = os.path.join(str(toy_archs), "no-such-arch.py")
    with pytest.raises(FileNotFoundError) as e:
        W.layout({"program_arch": "no-such-arch"})
    assert path in str(e.value)
