"""chip_smoke.py cannot pass without the chip: off a TPU it exits non-zero
naming what it found, and its rehearsal — the same code at tiny sizes on
the CPU — completes but never exits 0."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "chip_smoke.py")


@pytest.fixture
def chip_smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # in-process runs must not point the rest of the suite at the
    # checkout's persistent compile cache
    from paddle_tpu.utils import flags
    monkeypatch.setattr(flags, "set_compilation_cache_dir", lambda: None)
    return mod


def test_without_a_tpu_it_exits_nonzero_and_names_what_it_found(
        chip_smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code == chip_smoke.RC_NO_TPU != 0
    out, err = capsys.readouterr()
    assert out == ""                        # no result line of any kind
    assert "platform 'cpu'" in err and "JAX_PLATFORMS='cpu'" in err


def test_rehearsal_completes_on_cpu_and_never_exits_zero(chip_smoke, capsys):
    rc = chip_smoke.main(["--rehearsal", "--legs", "0,3"])
    assert rc == chip_smoke.RC_REHEARSAL_OK != 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln.get("leg") for ln in lines] == [0, 3, None]
    assert all(ln["rehearsal"] is True for ln in lines)
    summary = lines[-1]
    assert summary["ok"] is True and summary["claim"] is None
    assert summary["device"]["platform"] == "cpu"
    assert lines[1]["step_traces"] == 1
    assert lines[1]["mean_last5"] < 0.7 * lines[1]["mean_first5"]


def test_a_failed_leg_fails_the_run(chip_smoke, capsys, monkeypatch):
    monkeypatch.setattr(
        chip_smoke, "leg3_trainer",
        lambda rehearsal, cfg: (chip_smoke.emit(3, False, rehearsal), None))
    assert chip_smoke.main(["--rehearsal", "--legs", "0,3"]) == 1
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["ok"] is False


def test_last_line_on_the_chip_is_exactly_the_result_object(
        chip_smoke, capsys, monkeypatch):
    """The driver parses the last stdout line: ``ok`` and ``device`` and
    nothing else, ``device`` = platform/kind/count and nothing else."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "leg0_device_gate", lambda r: device)
    assert chip_smoke.main(["--legs", "0"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert json.loads(lines[-2])["claim"] is None
    monkeypatch.setattr(
        chip_smoke, "leg3_trainer",
        lambda rehearsal, cfg: (chip_smoke.emit(3, False, rehearsal), None))
    assert chip_smoke.main(["--legs", "0,3"]) == 1
    last = capsys.readouterr().out.splitlines()[-1]
    assert json.loads(last) == {"ok": False, "device": device}


@pytest.mark.slow
def test_full_rehearsal_every_leg():
    """Every leg end to end (interpret-mode kernels, the tiny served trunk,
    the trainer, and leg 4 on the virtual CPU mesh) — ~1 min."""
    proc = subprocess.run([sys.executable, _SCRIPT, "--rehearsal"],
                          capture_output=True, text=True, timeout=900,
                          cwd=_ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 10, proc.stderr[-3000:]
    summary = json.loads(proc.stdout.splitlines()[-1])
    assert summary["legs"] == {"0": True, "1": True, "2": True, "3": True,
                               "4": True}


def test_decode_engine_raises_on_an_unknown_device_kind(monkeypatch):
    """The restore/handoff routers price their choice with the peaks of
    the chip they run on; a chip that is not in the table is an error, not
    "v5e"."""
    import jax
    from paddle_tpu.models import transformer
    from paddle_tpu.serving.decode_engine import DecodeEngine
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=32,
                              trg_vocab=1, d_model=16, num_heads=2, dff=32,
                              enc_layers=1, dec_layers=0, max_len=32)
    eng = DecodeEngine(params, num_heads=2, num_slots=2, max_len=32,
                       kv_layout="paged", kv_block_size=8, prefill_chunk=4,
                       kv_host_bytes=1 << 20, warm=False)
    verdict, restore_ms, recompute_ms = eng._restore_predicted_faster(16)
    assert restore_ms > 0 and recompute_ms > 0      # the cpu row is known
    stranger = types.SimpleNamespace(device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [stranger])
    with pytest.raises(KeyError, match="TPU v99"):
        eng._restore_predicted_faster(16)
    with pytest.raises(KeyError, match="TPU v99"):
        eng._handoff_predicted_faster(16)
