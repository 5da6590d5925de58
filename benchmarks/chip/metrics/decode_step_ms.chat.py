"""decode_step_ms.chat: mean host time of an engine pump that emitted
tokens (adopt, decode step, readback, retire)."""


def read(cell):
    xs = [t1 - t0 for t0, t1, _, _ in cell.pumps]
    return sum(xs) / len(xs) * 1e3 if xs else None
