"""``chip_smoke.py`` without the chip: it refuses to run on a CPU, and its
phases run end to end at the reduced size with the pallas_interpret library
(the first rehearsal of the on-chip guide)."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod       # dataclasses resolve their module
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules["chip_smoke"]


REDUCED = dict(reduced=True, paper_n=1 << 12, batch=4, requests=4,
               prompt_len=16, gen_len=8, train_steps=4, train_batch=4,
               train_seq=64, train_lr=1e-2)


def test_chip_smoke_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_phases_rehearse_on_cpu(chip_smoke, bind_library,
                                           tmp_path, capsys):
    sizes = chip_smoke.Sizes(**REDUCED)
    # a private build root: other test workers may be importing the shared one
    lib = chip_smoke.phase_library("pallas_interpret", build_root=tmp_path)
    chip_smoke.phase_paper_app(lib, sizes, seed=0)
    chip_smoke.phase_serve(sizes, seed=0)
    chip_smoke.phase_train(sizes)
    out = capsys.readouterr().out
    for phase in ("library", "paper_app", "serve", "train"):
        tag = f"[{phase}] platform=cpu kind='cpu' count=1 "
        lines = [ln for ln in out.splitlines() if ln.startswith(tag)]
        assert lines and lines[-1].endswith(" ok"), (phase, lines)


def test_greedy_check_accepts_a_near_tie_only(chip_smoke):
    """Where two paths' greedy streams first differ, the picks must be
    within TIE_FACTOR x the paths' logit difference."""
    prompt = np.array([1, 2, 3])
    base = np.zeros((5, 4), np.float32)
    base[:, 0] = 1.0
    near = base.copy()
    near[3, 2] = 1.0 - 1e-3           # token 2 almost ties token 0 at step 1
    nudged = near.copy()
    nudged[3, 2] += 2e-3               # the other path tips it over

    def fwd(logits):
        return lambda seq: logits[:len(seq)]

    same = chip_smoke.check_greedy([0, 0], [0, 0], prompt, fwd(base),
                                   fwd(base))
    assert same.startswith("2/2 tokens equal")
    tie = chip_smoke.check_greedy([0, 0], [0, 2], prompt, fwd(near),
                                  fwd(nudged))
    assert tie.startswith("1/2 tokens equal, then a near tie")
    far = base.copy()
    far[3, 2] = -5.0
    with pytest.raises(AssertionError):
        chip_smoke.check_greedy([0, 0], [0, 2], prompt, fwd(far),
                                fwd(far + 1e-3))
