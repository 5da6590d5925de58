"""What decides ``correct``: the timed path's outputs against the plain
float32 reference (``reference.py``), at the timed sizes.

Serving: a sample of the window's finished requests, drawn from the seed
and holding the longest one. The reference reads each prompt followed by
the tokens the engine served, and at every served position gives the gap
by which the served token's logit lies below its own best logit. The
number compared is the widest gap. Greedy decoding serves the program's
argmax, so a sound program only loses near-ties its bfloat16 rounding can
flip; a wrong row, position, cache or state serves tokens far below the
best.

Training: the window's first training job against plain AdamW on the
same weights and batches, over three steps: each step's loss, the first
step's clipped gradient (read back from the optimizer's first moment,
mu_1 = (1 - b1) g_1) and the change of the weights after three steps,
each per leaf, by the worst leaf.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

import reference as R
import traffic as TR
import weights as W


def _gap_fn(cfg: dict, control: bool):
    """jitted (params, seq [1, S], target [S], lo, hi) -> gaps [S]: at each
    position j in [lo, hi) the reference's best logit less its logit for
    target[j], the token served after position j (control: for the token
    the float8 reference ranks first there); -inf elsewhere."""
    import jax
    import jax.numpy as jnp

    def fn(params, seq, target, lo, hi):
        ref = R.logits(params, cfg, seq)[0]                 # [S, V]
        best = jnp.max(ref, -1)
        if control:
            target = jnp.argmax(R.logits(params, cfg, seq, quant=True)[0], -1)
        got = jnp.take_along_axis(ref, target[:, None], -1)[:, 0]
        j = jnp.arange(seq.shape[1])
        return jnp.where((j >= lo) & (j < hi), best - got, -jnp.inf)
    return jax.jit(fn)


def served_gaps(cfg: dict, mix: dict, seed: int, served: List,
                control: bool = False) -> Dict[str, float]:
    """Widest logit gap over the sampled requests (see module docstring).
    ``served`` holds the window's requests with their tokens."""
    import jax
    import jax.numpy as jnp
    done = [s for s in served if s.done]
    pick = [done[i] for i in TR.check_sample(done, mix, seed)]
    if not pick:
        return {"widest_logit_gap": math.inf, "checked_tokens": 0}
    params = W.make_params(cfg, seed, jax.devices()[0])
    fn = _gap_fn(cfg, control)
    width = mix["max_seq"]
    widest, tokens = -math.inf, 0
    for s in pick:
        toks = list(s.sr.tokens)
        p, n = s.prompt_len, len(toks)
        # served token i follows position p - 1 + i of prompt + tokens
        seq = np.zeros((1, width), np.int32)
        seq[0, :p] = s.req.prompt
        seq[0, p:p + n - 1] = toks[:-1]
        target = np.zeros((width,), np.int32)
        target[p - 1:p - 1 + n] = toks
        gaps = fn(params, jnp.asarray(seq), jnp.asarray(target), p - 1,
                  p - 1 + n)
        widest = max(widest, float(jnp.max(gaps)))
        tokens += n
    del params
    return {"widest_logit_gap": widest, "checked_tokens": tokens}


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
               keep=None) -> float:
    """Worst leaf of |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = float(np.median(list(ref.values())))
    keys = [k for k in ref if keep is None or k in keep]
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def reference_train(cell, job_seed: int, quant=False, rows=None):
    """The reference's readings of a training job's first three steps:
    (losses, first clipped gradient's leaf norms, leaf norms of the change
    after three steps). ``quant`` and ``rows`` (a cut of the batch) serve
    the control and the planted faults."""
    import jax
    import jax.numpy as jnp
    tr = cell.mix["train"]
    opt = dict(tr["optimizer"], total_steps=tr["steps"])
    batches = [(jnp.asarray(x[:rows]), jnp.asarray(y[:rows]))
               for x, y in cell.train_batches(job_seed)[:3]]
    p0 = W.make_params(cell.tcfg, job_seed, jax.devices()[0])
    losses, clipped, p3 = R.adamw_steps(p0, cell.tcfg, opt, batches, quant)

    def norms(tree):
        return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(
            jnp.square(x.astype(jnp.float32)))))
            for k, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    g1 = norms(clipped[0])
    upd = norms(jax.tree_util.tree_map(
        lambda a, b: a - b.astype(jnp.float32), p3, p0))
    return losses, g1, upd


def train_gaps_between(ref, got) -> Dict[str, float]:
    """The three training numbers of ``got`` (losses, gradient and change
    norms) against the reference's ``ref``."""
    losses, g1, upd = ref
    med = float(np.median(list(g1.values())))
    # leaves whose gradient is nought to rounding move under Adam by
    # round-off alone: the change is compared on the others
    moving = {k for k, v in g1.items() if v >= 1e-3 * med}
    return {
        "train_loss_gap": max(abs(a - b) / abs(b)
                              for a, b in zip(got[0][:3], losses)),
        "train_grad_norm_gap": _leaf_gaps(got[1], g1),
        "train_update_norm_gap": _leaf_gaps(got[2], upd, moving),
    }


def train_gaps(cell, job) -> Dict[str, float]:
    """The first training job of the window against the reference."""
    b1 = cell.mix["train"]["optimizer"]["b1"]
    prog_g = {k: v / (1 - b1) for k, v in job.grad_norms.items()}
    return train_gaps_between(reference_train(cell, job.seed),
                              (job.losses, prog_g, job.update_norms))
