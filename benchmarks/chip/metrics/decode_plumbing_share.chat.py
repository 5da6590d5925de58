"""decode_plumbing_share.chat: the share of the decode program's op self
time (the window's jit__decode modules on the first device; an op's self
time leaves out the ops nested in it, as a layer scan's body ops are in
its while op) that lies outside every named scope of the model: the layer
scans' slicing of stacked weights and state, the stacking of the new
state, copies and loop bookkeeping. Ops are joined to their scopes by
instruction name with the op_name metadata of the decode program, compiled
again from its shapes after the window."""
import spantrace
import weights as W

from harness import DECODE_MODULE


def read(cell):
    tr = cell.trace_data
    if tr is None or not tr["devices"]:
        return None
    import jax
    hlo = spantrace.decode_hlo_text(cell.pc, W.abstract_params(cell.cfg),
                                    cell.mix["rows"], cell.mix["max_seq"],
                                    jax.devices()[0])
    if hlo is None:                 # a program without the model's scopes
        return None
    scopes = spantrace.decode_scopes(tr, hlo, DECODE_MODULE)
    total = sum(scopes.values())
    if not total or scopes.get(spantrace.UNMAPPED, 0.0) > 0.1 * total:
        return None                 # the trace's ops are not this program's
    return spantrace.plumbing_share(scopes)
