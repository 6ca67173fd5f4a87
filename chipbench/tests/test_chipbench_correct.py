"""`correct` at a tiny size on the CPU: the sound program passes against
the float32 reference, and the float8 control and every fault planted
under the timed path fail.  The limits of the tiny cell are set as the
cells' are (tinycell.TINY_LIMITS)."""
import json

import jax
import pytest

import compare
import reference
import run
from tinycell import TINY_LIMITS
from model import load_config
from traffic import Traffic, load_mix

SEED = 2 ** 31 + 7


def _run(root, capsys, plant=None, seed=SEED):
    rc = run.main(["--workload", "tiny.w", "--seed", str(seed),
                   "--seconds", "0.2", "--trace", "0"], root=root,
                  devices=jax.devices()[:1], plant=plant)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_program_is_correct(tiny_root, capsys):
    out = _run(tiny_root, capsys)
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["metrics"]["tokens_per_s"]["value"] > 0


def _state_unchanged(trainer):
    apply = trainer._apply_jit

    def unchanged(params, opt_state, grads):
        _, _, om = apply(params, opt_state, grads)
        return params, opt_state, om
    trainer._apply_jit = unchanged


def _half_batch(trainer):
    plan_step = trainer.sched.plan_step

    def half(step):
        plan = plan_step(step)
        keep = plan.waves[: max(1, len(plan.waves) // 2)]
        plan.waves = keep
        plan.denom = sum(p.length for w in keep for s in w.slots for p in s)
        return plan
    trainer.sched.plan_step = half


def _answer_altered(trainer):
    grad_step = trainer.grad_step

    def doubled(params, acc, batch, rt):
        new, metrics = grad_step(params, acc, batch, rt)
        new = jax.tree.map(lambda n, a: 2 * n - a, new, acc)
        return new, {**metrics, "loss": 2 * metrics["loss"]}
    trainer.grad_step = doubled


def _gradient_scaled(trainer):
    """Every wave's gradient counted twice and its loss once: a token
    count that divides the gradient alone off by two, which clipping hides
    from every number but the gradient norm before it."""
    grad_step = trainer.grad_step

    def scaled(params, acc, batch, rt):
        new, metrics = grad_step(params, acc, batch, rt)
        return jax.tree.map(lambda n, a: 2 * n - a, new, acc), metrics
    trainer.grad_step = scaled


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch,
                                   _answer_altered, _gradient_scaled])
def test_planted_fault_is_not_correct(tiny_root, capsys, plant):
    out = _run(tiny_root, capsys, plant=plant)
    assert out["correct"] is False, out["checks"]


def test_float8_control_is_not_correct(tiny_root):
    c = load_config("tiny", tiny_root / "chipbench")
    tr = Traffic(load_mix("tiny", tiny_root / "chipbench"), c["vocab_size"],
                 SEED)
    devs = jax.devices()[:1]
    ref = reference.Reference(c, tr, devs).run(SEED, run.CHECK_STEPS)
    ctl = reference.Reference(c, tr, devs, control=True).run(
        SEED, run.CHECK_STEPS)
    correct, checks = compare.verdict(compare.gaps(ctl, ref), TINY_LIMITS)
    assert correct is False, checks


def test_calibrate_reads_both_sides(tiny_root, capsys):
    import calibrate
    rc = calibrate.main(["--workload", "tiny.w", "--seeds", str(SEED),
                         "--control-seeds", str(SEED)], root=tiny_root,
                        devices=jax.devices()[:1])
    assert rc == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    read = {d["kind"]: d for d in lines}
    assert list(read) == ["program", "control", *reference.FAULTS]
    assert compare.verdict(read["program"], TINY_LIMITS)[0] is True
    for kind in ["control", *reference.FAULTS]:
        assert compare.verdict(read[kind], TINY_LIMITS)[0] is False, kind
    # clipping engages, so the scaled gradient shows in its norm alone
    scale = read["scale"]
    assert min(scale["ref_gnorm"]) > 1
    assert scale["gnorm_gap"] == pytest.approx(1.0, rel=1e-5)
    assert scale["grad_gap"] < TINY_LIMITS["grad_gap"]


def test_null_limit_is_printed_not_compared():
    values = {"loss_gap": 1e-5, "gnorm_gap": 0.5, "grad_gap": 1e-5,
              "update_gap": 1e-5}
    limits = dict(TINY_LIMITS, gnorm_gap=None)
    correct, checks = compare.verdict(values, limits)
    assert correct is True
    assert checks["gnorm_gap"] == {"value": 0.5, "limit": None}
    assert compare.verdict(values, TINY_LIMITS)[0] is False
