"""chip_smoke.py off the chip: it must refuse a CPU, and its control flow is
rehearsed on the CPU so later PRs cannot rot the script unnoticed.

The rehearsal steers everything from here — a tiny inline model, the Pallas
kernels in interpret mode, the device check relaxed — through the script's
module-level names, never through an option of the script."""

import functools
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_a_cpu():
    """The contract's negative half: no accelerator → non-zero exit and no
    result line, whatever else it printed."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode != 0, p.stdout
    assert '"ok"' not in p.stdout, p.stdout
    assert "needs a tpu device" in p.stderr, p.stderr[-500:]


@pytest.fixture()
def smoke(monkeypatch):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    from tensorlink_tpu.engine import continuous, paged
    from tensorlink_tpu.models import ModelConfig
    from tensorlink_tpu.ops import attention

    tiny = ModelConfig(
        family="qwen3", vocab_size=260, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, max_seq_len=256, qk_norm=True,
        tie_embeddings=True, dtype=jnp.float32,
    )
    monkeypatch.setattr(chip_smoke, "MODEL", "smoke-tiny")
    monkeypatch.setattr(chip_smoke, "MODEL_CONFIG", tiny.to_json())
    monkeypatch.setattr(chip_smoke, "SEQ_LEN", 256)
    monkeypatch.setattr(chip_smoke, "PLATFORM", "cpu")
    # interpret mode lowers the kernel to plain HLO: there is no custom call
    # to find, so the rehearsal only proves the step programs lower
    monkeypatch.setattr(
        chip_smoke, "kernel_in_program",
        lambda cont: all(cont.lower_step(w).as_text()
                         for w in cont.block_widths),
    )
    # the kernels, interpreted — both where the smoke calls them and where
    # the step program picked them up at import
    for name in ("ragged_paged_attention", "paged_attention"):
        interp = functools.partial(getattr(attention, name), interpret=True)
        monkeypatch.setattr(attention, name, interp)
        if hasattr(paged, name):
            monkeypatch.setattr(paged, name, interp)
    # ...and the engine told to use them though this is no TPU
    init = continuous.ContinuousEngine.__init__

    def init_with_kernel(self, *a, **kw):
        init(self, *a, **kw)
        self.use_kernel = True

    monkeypatch.setattr(continuous.ContinuousEngine, "__init__", init_with_kernel)
    submit = continuous.ContinuousEngine.submit
    yield chip_smoke
    continuous.ContinuousEngine.submit = submit  # undo tap_streams()


@pytest.mark.slow  # a two-node cluster + interpret-mode kernels, ~minutes
def test_chip_smoke_control_flow_on_cpu(smoke, capsys):
    assert smoke.main([]) == 0
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    # every phase ran and said so
    for needle in ("phase kernels:", "phase serve:", "requests:", "checks:",
                   "page conservation clean",
                   "no program built after warm-up",
                   "compile set unchanged since warm-up",
                   "compile cache:"):
        assert needle in out, (needle, out[-3000:])


@pytest.mark.slow  # as above, on four virtual devices
def test_chip_smoke_tp4_control_flow_on_cpu(smoke, monkeypatch, capsys):
    """``--chips 4`` at a tiny Qwen2.5 shape (seven query heads on each of
    four kv heads, q/k/v biases, an untied head): the sharded load, the
    traffic, the memory spread and the logits comparison all run."""
    from tensorlink_tpu.models import ModelConfig

    tiny = ModelConfig(
        family="qwen2", vocab_size=260, d_model=112, n_layers=2, n_heads=28,
        n_kv_heads=4, head_dim=16, d_ff=128, max_seq_len=256, attn_bias=True,
        tie_embeddings=False, dtype=jnp.float32,
    )
    monkeypatch.setattr(smoke, "MODEL_CONFIG", tiny.to_json())
    monkeypatch.setattr(smoke, "LOGIT_CASES", ((40, 8, 4), (200, 8, 1)))
    assert smoke.main(["--chips", "4"]) == 0
    out = capsys.readouterr().out
    assert json.loads(out.strip().splitlines()[-1])["ok"] is True
    for needle in ("phase tp=4:", "engine runs tensor_parallel=4",
                   "weights spread evenly", "phase logits:",
                   "control: a reference without the biases",
                   "served argmax within", "no logit further than"):
        assert needle in out, (needle, out[-3000:])
