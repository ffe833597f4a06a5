"""Each kind's code driven end to end on the CPU at a tiny size.

The command refuses to run off a TPU; here the kinds' ``run`` is called
directly, with the configuration cut to a size a test can hold: blocked
Cholesky at n=256 and 384, a two-layer Mamba-2 of width 64 on four
slots.  Then the same run with the program broken underneath must come
out not correct.
"""

import copy
import time

from common import BENCH, load_json, load_module

SEED = 2 ** 33 + 12345          # a seed wider than 32 bits


def blocked_case():
    sizes = load_json(BENCH / "configs" / "cholesky.json")
    # on the CPU at n=256 the models are rough and the candidates' times
    # noisy, so the pick is held to a wider regret than on the chip; a
    # block size of 16 makes a wrong pick far slower than that
    sizes.update(sizes=[256, 384], block_sizes=[16, 128], regret_size=256,
                 regret_repetitions=3, regret_limit=1.0,
                 generator=dict(sizes["generator"], repetitions=2,
                                max_points=6, oversampling=1))
    return dict(cell={"name": "cholesky.solve", "chips": 1},
                config={"name": "cholesky",
                        "file": "chipbench/configs/cholesky.json"},
                sizes=sizes, mix=load_json(BENCH / "traffic" / "solve.json"))


def serve_case():
    sizes = load_json(BENCH / "configs" / "mamba2-2.7b.json")
    sizes.update(n_layers=2, d_model=64, vocab=512, ssm_state=16,
                 ssm_heads=8, ssm_head_dim=16, ssm_chunk=32, slots=4,
                 ctx_len=64)
    mix = load_json(BENCH / "traffic" / "short_chat.json")
    mix.update(rate_per_s=8.0, ramp_s=0.5, tail_s=0.5, check_every=4,
               check_longest=1,
               prompt_tokens=dict(mix["prompt_tokens"], mean=8, max=20),
               output_tokens=dict(mix["output_tokens"], mean=6, max=12))
    return dict(cell={"name": "mamba2-2.7b.short_chat", "chips": 1},
                config={"name": "mamba2-2.7b",
                        "file": "chipbench/configs/mamba2-2.7b.json"},
                sizes=sizes, mix=mix)


def drive(case, seconds=1.0, seed=SEED):
    kind = load_module(BENCH / "kinds" / f"{case['sizes']['kind']}.py")
    return kind.run(**copy.deepcopy(case), seed=seed, seconds=seconds,
                    trace=False, t_start=time.perf_counter())


def test_blocked_runs_and_is_correct(state):
    out = drive(blocked_case())
    assert out.check.correct, out.check.items
    assert out.e2e["blocked_gflop_per_s"] > 0 and out.e2e["setup_s"] > 0
    assert out.attempted == out.counters["problems"] >= 2
    assert out.notes["compiles_in_window"] == 0
    assert (state / "models" / "cholesky.json").exists()
    # the second run loads the stored models
    assert drive(blocked_case()).notes["model_generation_s"] == 0.0


def test_serve_runs_and_is_correct(state):
    out = drive(serve_case(), seconds=2.0)
    assert out.check.correct, out.check.items
    assert out.notes["checked"] >= 2 and out.notes["checked_tokens"] >= 6
    assert set(out.notes["logit"]) == {"row_max", "row_mean", "global"}
    assert out.e2e["serve_out_tokens_per_s"] > 0
    assert out.e2e["serve_ttft_p90_s"] > 0 and out.e2e["serve_itl_p90_ms"] > 0
    assert out.failed == 0 and out.attempted > 0
    lanes = out.counters["lane_steps"]
    assert lanes["prefill"] > 0 and lanes["decode"] > 0
    assert out.notes["compiles_in_window"] == 0


# ---------------------------------------------------------------- faults --
# The timed path broken underneath must make ``correct`` false: each
# fault a cell can have.  Half of a batch left out of a mean, and the
# exchange between chips, do not exist in these one-chip cells.

def test_blocked_answer_altered_where_produced(state, monkeypatch):
    from repro.dla import ExecEngine
    orig = ExecEngine.potf2

    def potf2(self, uplo, A):
        orig(self, uplo, A)
        self.mats[A.mat][A.r0, A.c0] *= 1.01

    monkeypatch.setattr(ExecEngine, "potf2", potf2)
    out = drive(blocked_case())
    assert not out.check.correct, out.check.items


def test_blocked_pick_altered_where_produced(state, monkeypatch):
    # the selection's pick replaced by the last variant at the smallest
    # block size
    import repro.core.selection as selection
    pick = selection.optimize_algorithm_and_block_size

    def altered(tracers, models, n, block_sizes, **kw):
        name, b, t = pick(tracers, models, n, block_sizes, **kw)
        return list(tracers)[-1], min(block_sizes), t

    monkeypatch.setattr(selection, "optimize_algorithm_and_block_size",
                        altered)
    out = drive(blocked_case())
    assert not out.check.correct, out.check.items
    assert out.check.items["pick_regret"]["value"] > \
        out.check.items["pick_regret"]["limit"]


def test_blocked_update_that_leaves_its_matrix_unchanged(state, monkeypatch):
    from repro.dla import ExecEngine
    monkeypatch.setattr(ExecEngine, "syrk", lambda self, *a: None)
    out = drive(blocked_case())
    assert not out.check.correct, out.check.items


def test_serve_token_altered_where_produced(state, monkeypatch):
    from repro.serve import ServeEngine
    import controls
    monkeypatch.setattr(ServeEngine, "advance", ServeEngine.advance)
    controls.alter_tokens()          # the fault controls.py plants
    out = drive(serve_case(), seconds=2.0)
    assert not out.check.correct, out.check.items
    assert out.check.items["served_gap"]["value"] > \
        out.check.items["served_gap"]["limit"]


def test_serve_step_that_returns_its_state_unchanged(state, monkeypatch):
    import repro.serve.engine as engine
    step = engine.decode_step
    monkeypatch.setattr(engine, "decode_step",
                        lambda cfg, params, caches, token, index:
                        (step(cfg, params, caches, token, index)[0], caches))
    out = drive(serve_case(), seconds=2.0)
    assert not out.check.correct, out.check.items


# --------------------------------------------------------------- control --
# The control, the reference in the precision below the configuration's,
# fails the limit at a size a test can hold.  On the chip it is read at
# the cells' own sizes by ``chipbench/controls.py``.

def test_blocked_control_fails_the_limit(state):
    kind = load_module(BENCH / "kinds" / "blocked.py")
    out = kind.run(**copy.deepcopy(blocked_case()), seed=SEED, seconds=1.5,
                   trace=False, t_start=time.perf_counter(), hold=True)
    ref = out.held["ref"]
    assert out.check.correct, out.check.items
    assert kind.control(out.held)["factor_err"] > 3 * ref.FACTOR_LIMIT


def test_serve_control_stands_apart_from_the_program(state):
    # at two layers of width 64 the control's logits lie far closer to
    # the reference's than at the cell's 64 layers of width 2560, so its
    # reading is held against the program's, not against the limit
    kind = load_module(BENCH / "kinds" / "serve.py")
    out = kind.run(**copy.deepcopy(serve_case()), seed=SEED, seconds=2.0,
                   trace=False, t_start=time.perf_counter(), hold=True)
    assert out.check.correct
    program = out.check.items["logit_err"]["value"]
    assert kind.control(out.held)["logit_err"] > 3 * program
