"""The hybrid (zamba2) decode step: continuous-batching parity with the
full-sequence forward, and the structure that keeps its stacked weights
and states in place."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_arch
from repro.models import decode as D
from repro.models import model as M
from repro.serve.decode import make_prefill_step


def test_per_row_decode_matches_forward():
    """Rows prefilled at different prompt lengths decode together with a
    per-row ``pos`` vector, as the serve engine runs them: every step's
    logits match the forward's at each row's position, and the caches the
    steps return match the forward's ``collect_cache`` (bf16 weights and
    caches, as served)."""
    cfg = get_arch("zamba2-2.7b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    rng = np.random.default_rng(0)
    lengths = np.array([4, 12, 20])
    steps, max_seq = 12, 40     # every length + steps stays within a chunk
    toks = rng.integers(0, cfg.vocab, (len(lengths), 32)).astype(np.int32)

    prefill = jax.jit(make_prefill_step(cfg, attn_impl="naive"))
    decode = jax.jit(lambda p, c, t, pos: D.decode_step(p, cfg, c, t, pos))
    cache = D.init_cache(cfg, len(lengths), max_seq)
    for b, n in enumerate(lengths):
        _, row = prefill(params, {"tokens": jnp.asarray(toks[b:b + 1, :n])})
        cache = D.cache_insert(cache, row, b)

    hidden, _ = M.forward(params, cfg, {"tokens": jnp.asarray(toks)},
                          attn_impl="naive")
    ref = np.asarray(M.logits_from_hidden(cfg, params, hidden))
    rows = np.arange(len(lengths))
    pos = lengths.copy()
    for _ in range(steps):
        logits, cache = decode(params, cache, jnp.asarray(toks[rows, pos]),
                               jnp.asarray(pos, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits), ref[rows, pos],
                                   rtol=2e-2, atol=0.1)
        pos += 1

    for b, n in enumerate(lengths):
        _, _, want = M.forward(params, cfg,
                               {"tokens": jnp.asarray(toks[b:b + 1,
                                                           :n + steps])},
                               attn_impl="naive", collect_cache=True)
        got = {k: np.asarray(t, np.float32)
               for k, t in D.cache_extract(cache, b).items()}
        for k in ("k", "v"):    # slots past the row's length stay empty
            assert not got[k][..., n + steps:, :].any()
            got[k] = got[k][..., :n + steps, :]
        for k, atol in (("m_conv", 0.08), ("m_ssm", 0.03), ("k", 0.08),
                        ("v", 0.08)):
            np.testing.assert_allclose(got[k], np.asarray(want[k], np.float32),
                                       rtol=2e-2, atol=atol, err_msg=k)


# primitives that only move or select data: a leaf stays itself through them
_MOVES = {"reshape", "transpose", "squeeze", "broadcast_in_dim",
          "convert_element_type", "copy", "slice", "dynamic_slice", "gather",
          "concatenate"}


def _scanned_leaves(fn, *args):
    """The argument leaves (by path) that reach a ``lax.scan`` as ``xs``,
    directly or through data movement, at any depth of ``fn``'s jaxpr."""
    closed = jax.make_jaxpr(fn)(*args)
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(args)[0]]
    found = set()

    def walk(jaxpr, labels):
        for eqn in jaxpr.eqns:
            ins = [labels.get(v) if not hasattr(v, "val") else None
                   for v in eqn.invars]
            if eqn.primitive.name == "scan":
                n = eqn.params["num_consts"] + eqn.params["num_carry"]
                found.update(l for l in ins[n:] if l)
            if eqn.primitive.name in _MOVES and any(ins):
                labels.update((o, next(l for l in ins if l))
                              for o in eqn.outvars)
            for sub in eqn.params.values():
                sub = getattr(sub, "jaxpr", sub)
                for s in sub if isinstance(sub, (tuple, list)) else (sub,):
                    s = getattr(s, "jaxpr", s)
                    if hasattr(s, "eqns"):
                        k = len(s.invars)
                        walk(s, {v: l for v, l in zip(s.invars, ins[-k:])
                                 if l})
    walk(closed.jaxpr, dict(zip(closed.jaxpr.invars, paths)))
    return found


def test_hybrid_decode_scans_no_weight_or_cache():
    """The hybrid decode step reads its stacked weights and states in place:
    no weight leaf and no cache leaf is a scan's ``xs``. Scanned, XLA
    slices each group out of its stack and copies it to another layout for
    the dots, every step. The forward (prefill, training) still scans its
    weights, which shows the check sees them."""
    cfg = get_arch("zamba2-2.7b").reduced()
    params = jax.eval_shape(lambda k: M.init_params(cfg, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: D.init_cache(cfg, 2, 64))
    rows = jax.ShapeDtypeStruct((2,), jnp.int32)
    assert _scanned_leaves(
        lambda p, c, t, pos: D.decode_step(p, cfg, c, t, pos),
        params, cache, rows, rows) == set()
    scanned = _scanned_leaves(
        lambda p, t: M.forward(p, cfg, {"tokens": t}, attn_impl="naive"),
        params, jax.ShapeDtypeStruct((1, 32), jnp.int32))
    assert "[0]['groups']['mamba']['in_proj']" in scanned
