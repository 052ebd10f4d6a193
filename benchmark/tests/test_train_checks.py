"""What decides ``correct`` in the training cell: the reference's margins
against a hand computation, the loss limit read from them, ``loss_fell``
judged only in a window long enough to learn, and the seed sweep.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import arith, harness  # noqa: E402


def _testdata(*parts):
    with open(os.path.join(BENCH, "testdata", *parts)) as f:
        return json.load(f)


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def test_reference_margins_against_a_hand_computation():
    """One layer of 3 units, 3 classes, 2 rows of 4 tokens, in numpy."""
    from benchmark.reference import lstm
    rng = np.random.RandomState(4)
    h, e, v, b, t, c = 3, 2, 7, 2, 4, 3
    p = {"emb": rng.randn(v, e), "w_out": rng.randn(h, c),
         "b_out": rng.randn(c),
         "lstm": [{"w_in": rng.randn(e, 4 * h), "w_r": rng.randn(h, 4 * h),
                   "b7": rng.randn(7 * h)}]}
    tokens, labels = rng.randint(0, v, (b, t)), np.array([2, 0])
    lyr = p["lstm"][0]
    bias, (p_i, p_f, p_o) = lyr["b7"][:4 * h], np.split(lyr["b7"][4 * h:], 3)
    hh, cc = np.zeros((b, h)), np.zeros((b, h))
    for step in range(t):
        g = p["emb"][tokens[:, step]] @ lyr["w_in"] + hh @ lyr["w_r"] + bias
        a, gi, gf, go = np.split(g, 4, axis=-1)
        i, f = _sigmoid(gi + cc * p_i), _sigmoid(gf + cc * p_f)
        cc = np.tanh(a) * i + cc * f
        hh = _sigmoid(go + cc * p_o) * np.tanh(cc)
    z = hh @ p["w_out"] + p["b_out"]
    want = np.array([
        z[r, labels[r]] - np.log(sum(np.exp(z[r, k]) for k in range(c)
                                     if k != labels[r])) for r in range(b)])
    want_loss = np.mean([-np.log(np.exp(z[r, labels[r]]) / np.exp(z[r]).sum())
                         for r in range(b)])
    as32 = {"emb": p["emb"].astype("f4"), "w_out": p["w_out"].astype("f4"),
            "b_out": p["b_out"].astype("f4"),
            "lstm": [{k: x.astype("f4") for k, x in lyr.items()}]}
    loss, margins = lstm.loss_and_margins(as32, tokens, labels)
    assert np.asarray(margins) == pytest.approx(want, abs=2e-5)
    assert float(loss) == pytest.approx(want_loss, abs=2e-5)
    assert float(lstm.loss(as32, tokens, labels)) == float(loss)


def test_loss_noise_worst_leaf_gap_and_steps_to_fall_by_hand():
    # rows with margins 0 and ln 3 put 1/2 and 1/4 off their label; stated
    # as 0.1 and ln 3 - 0.2 their shares of the loss error are 0.05, -0.05
    assert arith.loss_noise([0.0, math.log(3.0)],
                            [0.1, math.log(3.0) - 0.2]) == pytest.approx(0.05)
    assert arith.loss_noise([1.5, -2.0], [1.5, -2.0]) == 0.0
    # the median reference norm is 1.0: "small" is held against it, not
    # against its own 0.01; "big" against its own 4.0
    want = {"small": 0.01, "mid": 1.0, "big": 4.0}
    got = {"small": 0.11, "mid": 1.05, "big": 3.0}
    assert arith.worst_leaf_gap(got, want) == (pytest.approx(0.25), "big")
    assert arith.worst_leaf_gap(got, want, live=["small", "mid"]) == (
        pytest.approx(0.1), "small")
    # the mean of the last 3 first lies under 0.7 x 1.0 after 5 steps
    assert arith.steps_to_fall([1.0, 0.9, 0.8, 0.6, 0.5, 0.1], 1.0,
                               span=3) == 5
    assert arith.steps_to_fall([1.0] * 30, 1.0) is None
    assert arith.steps_to_fall([0.1] * 5, 1.0) is None      # under a span


def _run_tiny(capsys, traffic=None, optimizer=None, trainer=None,
              seed=3400000077, seconds=0.6):
    """drivers/train.run on the rehearsal's tiny cell: (obs, checks)."""
    from benchmark.drivers import train
    cfg = _testdata("configs", "tiny-lstm.json")
    cfg["optimizer"] = dict(cfg["optimizer"], **(optimizer or {}))
    cfg["trainer"] = trainer or {}
    obs = train.run({
        "cell": {"name": "tiny_train", "chips": 1}, "config": cfg,
        "traffic": dict(_testdata("traffic", "tiny_batches.json"),
                        **(traffic or {})),
        "seed": seed, "seconds": seconds, "trace": False,
        "rehearsal": True, "phases": harness.Phases(), "trace_dir": None})
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    checks, = [ln["checks"] for ln in lines if "checks" in ln]
    return obs, checks


def test_a_window_too_short_to_learn_does_not_judge_loss_fell(capsys):
    obs, checks = _run_tiny(capsys, traffic={"loss_fell_min_steps": 10 ** 9})
    assert checks["loss_fell"] is None and not checks["loss_fell_judged"]
    assert checks["loss_matches_reference"] and checks["losses_finite"]
    assert obs["correct"] is True
    assert obs["compared"]["mean_last20_loss"][1] is None
    assert checks["compared"] == obs["compared"]
    for name in ("loss_err_step0", "grad_norm_gap", "change_norm_gap",
                 "loss_abs_err"):
        value, limit = obs["compared"][name]
        assert 0.0 <= value <= limit
    # read and printed, not judged: no fault separates it from sound runs
    assert obs["compared"]["loss_err_step1"][1] is None


def test_a_long_enough_window_on_a_trainer_that_cannot_learn_is_not_correct(
        capsys):
    obs, checks = _run_tiny(capsys, traffic={"loss_fell_min_steps": 30},
                            optimizer={"learning_rate": 0.0})
    assert checks["steps"] >= 30 and checks["loss_fell_judged"]
    assert checks["loss_fell"] is False and checks["steps_to_fall"] is None
    assert checks["loss_matches_reference"]     # the forward is still right
    assert checks["first_steps_follow_reference"]   # and Adam at rate 0
    assert obs["correct"] is False
    value, limit = obs["compared"]["mean_last20_loss"]
    assert value >= limit == pytest.approx(0.7 * checks["first_loss"])


def test_seed_sweep_counts_and_margins_in_rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "seed_sweep.py"), "--workload",
         "tiny_train", "--start", "3400000005", "--seeds", "2", "--also",
         "1724374153", "--seconds", "0.5", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(ln) for ln in r.stdout.splitlines()]
    assert [x["seed"] for x in rows[:-1]] == [1724374153, 3400000005,
                                              3400000006]
    assert all(x["rc"] == harness.RC_REHEARSAL_OK and x["correct"]
               and x["checks"]["loss_matches_reference"] for x in rows[:-1])
    last = rows[-1]
    assert last["seeds"] == 3 and last["correct"] == 3
    # a reading of exactly 0 (float32 on the CPU can agree) has no margin
    margins = {x["seed"]: x["compared"]["loss_abs_err"][1]
               / x["compared"]["loss_abs_err"][0]
               for x in rows[:-1] if x["compared"]["loss_abs_err"][0]}
    if margins:
        margin, seed = last["smallest_margin"]["loss_abs_err"]
        assert margin == margins[seed] == min(margins.values()) > 2.0
    assert last["smallest_margin"]["step_traces"][0] == 1.0


# ------------------------------------------- the control and the faults

def _broken_step(monkeypatch, wrap):
    """The trainer's own step, ``wrap``ped: the rest of the run, reader,
    feed, handler, window and reference, goes on as it is."""
    from paddle_tpu.trainer.trainer import SGD
    real = SGD._dispatch_step
    monkeypatch.setattr(SGD, "_dispatch_step",
                        lambda self, feed: wrap(real(self, feed)))


def _failed(obs):
    return {name for name, (value, limit) in obs["compared"].items()
            if limit is not None and not value <= limit}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(step):
        def unchanged(params, opt_state, state, feed, rng):
            # copies: the real step donates what it is given
            kept = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
            _p, _o, new_state, cost, extras = step(params, opt_state, state,
                                                   feed, rng)
            return kept[0], kept[1], new_state, cost, extras
        return unchanged

    _broken_step(monkeypatch, wrap)
    obs, checks = _run_tiny(capsys)
    assert obs["correct"] is False
    assert not checks["first_steps_follow_reference"]
    # no moment, so no gradient, and nothing moved: both read 1
    assert obs["compared"]["grad_norm_gap"][0] == pytest.approx(1.0)
    assert obs["compared"]["change_norm_gap"][0] == pytest.approx(1.0)
    # the forward alone is right, on parameters that never moved
    assert checks["loss_matches_reference"]


def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    import jax

    def wrap(step):
        def half(params, opt_state, state, feed, rng):
            feed = jax.tree_util.tree_map(lambda x: x[:x.shape[0] // 2], feed)
            return step(params, opt_state, state, feed, rng)
        return half

    _broken_step(monkeypatch, wrap)
    obs, checks = _run_tiny(capsys)
    assert obs["correct"] is False
    assert {"grad_norm_gap", "change_norm_gap", "loss_abs_err"} <= _failed(obs)


@pytest.mark.parametrize("seed", [3400000011, 3400000012, 3400000013])
def test_the_control_int8_weights_is_not_correct(capsys, seed):
    """The program's own int8 path, SGD(quant_weights=True), in the place of
    the program the configuration states: the nearest precision below."""
    obs, checks = _run_tiny(capsys, trainer={"quant_weights": True},
                            seed=seed)
    assert obs["correct"] is False
    assert not checks["first_steps_follow_reference"]
    assert {"grad_norm_gap", "change_norm_gap"} <= _failed(obs)
    sound, _ = _run_tiny(capsys, seed=seed)
    assert sound["correct"] is True and not _failed(sound)


# ------------------------------------ the limits against the chip's readings

def _over_limit(row, rc):
    """The numbers of one row of testdata/lstm_check_readings.json that lie
    over the cell's limits, and the largest reading / limit of the row."""
    pairs = {"loss_abs_err": (row["loss_abs_err"],
                              arith.loss_limit(rc, row["noise"]))}
    if "grad_norm_gap" in row:      # the rows that followed the first steps
        pairs.update(
            grad_norm_gap=(row["grad_norm_gap"], rc["grad_norm_limit"]),
            change_norm_gap=(row["change_norm_gap"], rc["change_norm_limit"]),
            loss_err_step0=(row["loss_err_step0"],
                            rc["follow_loss_limits"][0]))
    return ({name for name, (v, lim) in pairs.items() if v > lim},
            max(v / lim for v, lim in pairs.values()))


def test_the_cells_limits_against_the_table_of_chip_readings():
    """Every sound run inside HALF of every limit, every wrong program
    outside one on as many of its seeds as the table recorded: a limit that
    is loosened, or a noise model that is changed, fails here first."""
    table = _testdata("lstm_check_readings.json")
    with open(os.path.join(BENCH, "configs", "lstm-imdb-h512.json")) as f:
        rc = json.load(f)["reference_check"]
    caught, sound = {}, {}
    for row in table["rows"]:
        over, worst = _over_limit(row, rc)
        key = row["program"], row["path"], row["source"]
        if row["program"] in ("right", "nopeep_all"):
            assert not over and worst <= 0.5, (row["seed"], key, worst)
            sound[key] = sound.get(key, 0) + 1
        else:
            caught.setdefault(key, []).append(over)
    assert sound == {("right", "kernel", "probe"): 48,
                     ("right", "scan", "probe"): 48,
                     ("nopeep_all", "kernel", "probe"): 12,
                     ("right", "kernel", "run"): 48}
    assert all(len(rows) == 12 for rows in caught.values())
    assert {(name, source): sum(bool(over) for over in rows)
            for (name, _path, source), rows in caught.items()} == {
        ("gates", "probe"): 12, ("half", "probe"): 12, ("nopeep", "probe"): 11,
        ("quant", "probe"): 10, ("int8", "probe"): 9, ("bf16c", "probe"): 5,
        ("gates", "run"): 12, ("quant", "run"): 12, ("int8", "run"): 10,
        ("nopeep", "run"): 8, ("bf16c", "run"): 2}
    # half of the batch left out fails EVERY number on every seed
    assert all(over == {"loss_abs_err", "loss_err_step0", "grad_norm_gap",
                        "change_norm_gap"}
               for over in caught["half", "kernel", "probe"])
    # the step-8 loss is what catches a wrong recurrence, and the control
    for key in (("gates", "kernel", "probe"), ("gates", "kernel", "run"),
                ("quant", "kernel", "run")):
        assert all("loss_abs_err" in over for over in caught[key])
