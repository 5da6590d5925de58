"""``chip_smoke.py`` rehearsed on the CPU at the reduced config: its serving,
co-resident training, placement and reference checks run as they do on the
chip, so a change that breaks the smoke fails here first. Only the memory
report is replaced, since it reads the chip's own counters
(``memory_stats()``), which the CPU backend does not keep. The four-chip
path needs four devices: it runs in a child with four virtual CPU devices,
so this process keeps its one."""
import math
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_one_chip_phase_at_reduced_size(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke as CS
    monkeypatch.setattr(CS, "memory_report", lambda sched, devs, label: None)
    out = CS.one_chip(0, full=False)
    assert out["tokens_served"] == 8 * CS.GEN_LEN
    assert len(out["tokens"]) == 8
    assert all(math.isfinite(x) for x in out["train_losses"])


def test_four_chip_phase_on_virtual_devices():
    code = textwrap.dedent("""
        import chip_smoke as CS
        CS.memory_report = lambda sched, devs, label: None
        out = CS.four_chip(0, full=False)
        assert len(out["tokens"]) == 16, out["tokens"]
        assert abs(out["gang_loss"] - out["single_loss"]) < 1e-3
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "src")]))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=280, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
