"""Pipeline parallelism: the GPipe schedule over pp×tp×dp must
reproduce the single-device loss and training step exactly (same
params, same batch, microbatching is loss-neutral).

The 1F1B tests run ISOLATED in a subprocess with retries: on this
sandbox's single CPU core, XLA CPU's collective rendezvous can rarely
starve ("Expected 8 threads to join the rendezvous, but only 6
arrived") and CHECK-aborts the whole process at its 40 s terminate
timeout — a runtime scheduling artifact, not a numerics bug (the same
programs pass deterministically on re-run). Isolation keeps a flaked
abort from killing the entire pytest run; the retry drops the ~20%
abort rate to ~1%."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import transformer as tf
from tpushare.models.pipeline import (build_interleaved_schedule,
                                      interleaved_layer_order,
                                      make_pp_train_step, param_specs,
                                      to_interleaved_storage)
from tpushare.models.training import lm_loss, sgd_train_step
from tpushare.parallel import make_mesh, shard_tree

CFG = tf.tiny(remat=False, n_layers=4)  # 4 layers -> 2 per pp stage


def _setup(batch=4, seq=16):
    params = tf.init_params(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (batch, seq)))
    return params, toks


def test_pp_tp_dp_step_matches_single_device():
    params, toks = _setup()
    ref_params, ref_loss = sgd_train_step(params, toks, CFG, lr=0.1)

    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    step = make_pp_train_step(CFG, mesh, n_microbatches=2, lr=0.1)
    sharded = shard_tree(params, mesh, param_specs(CFG))
    new_params, loss = step(sharded, toks)

    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
        new_params, ref_params)


def test_pp_only_four_stages():
    # 4 stages x 1 layer each, 4 microbatches; loss must still match.
    params, toks = _setup(batch=4)
    ref_loss = lm_loss(params, toks, CFG)
    mesh = make_mesh({"pp": 4, "tp": -1})
    step = make_pp_train_step(CFG, mesh, n_microbatches=4, lr=0.0)
    sharded = shard_tree(params, mesh, param_specs(CFG))
    _, loss = step(sharded, toks)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)


def _run_isolated(body_name: str, attempts: int = 3) -> None:
    """Execute ``body_name`` (a module-level _body_* function) in a
    fresh subprocess, retrying on the XLA CPU rendezvous SIGABRT."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # The CPU pin runs FIRST in the child: config.update wins over
    # whatever JAX_PLATFORMS the child inherits.
    code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
            f"import tests.test_pipeline as m; m.{body_name}()")
    last = None
    for attempt in range(attempts):
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=repo, capture_output=True,
            text=True,
            env={**os.environ,
                 "PYTHONPATH": repo + os.pathsep
                 + os.environ.get("PYTHONPATH", "")})
        if proc.returncode == 0:
            if attempt:
                # Flake accounting (VERDICT r2 item 7): make retry
                # consumption visible in the pytest -s / CI log so a
                # rising SIGABRT rate is noticed, not silently eaten.
                print(f"[flake-retry] {body_name}: passed on attempt "
                      f"{attempt + 1}/{attempts} after {attempt} "
                      f"rendezvous SIGABRT(s)", file=sys.stderr)
            return
        last = proc
        if proc.returncode != -6 and proc.returncode != 134:
            break                      # real failure: don't mask it
        tail = ("retrying" if attempt + 1 < attempts
                else "attempts exhausted")
        print(f"[flake-retry] {body_name}: attempt {attempt + 1} died "
              f"rc={proc.returncode} (XLA CPU rendezvous SIGABRT); "
              f"{tail}", file=sys.stderr)
    raise AssertionError(
        f"{body_name} rc={last.returncode}"
        f"\n{last.stdout}\n{last.stderr}")


def _body_1f1b_step_matches_single_device():
    # The manual-VJP 1F1B schedule must reproduce the same step as the
    # autodiff GPipe path and the single-device reference.
    params, toks = _setup()
    ref_params, ref_loss = sgd_train_step(params, toks, CFG, lr=0.1)

    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    step = make_pp_train_step(CFG, mesh, n_microbatches=2, lr=0.1,
                              schedule="1f1b")
    sharded = shard_tree(params, mesh, param_specs(CFG))
    new_params, loss = step(sharded, toks)

    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
        new_params, ref_params)


def test_1f1b_step_matches_single_device():
    _run_isolated("_body_1f1b_step_matches_single_device")


def _body_1f1b_four_stages_m_gt_2p():
    # M=8 > 2P-1=7: the residual ring wraps; loss must still match.
    params, toks = _setup(batch=8)
    ref_loss = lm_loss(params, toks, CFG)
    mesh = make_mesh({"pp": 4, "tp": -1})
    step = make_pp_train_step(CFG, mesh, n_microbatches=8, lr=0.0,
                              schedule="1f1b")
    sharded = shard_tree(params, mesh, param_specs(CFG))
    _, loss = step(sharded, toks)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)


def test_1f1b_four_stages_m_gt_2p():
    _run_isolated("_body_1f1b_four_stages_m_gt_2p")


def _body_interleaved_step_matches_single_device():
    # Megatron interleaved virtual stages (v=2 chunks/rank) must
    # reproduce the single-device step exactly; params/grads live in
    # interleaved storage order, so the reference is permuted too.
    params, toks = _setup()
    ref_params, ref_loss = sgd_train_step(params, toks, CFG, lr=0.1)

    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    step = make_pp_train_step(CFG, mesh, n_microbatches=2, lr=0.1,
                              schedule="interleaved", n_chunks=2)
    sharded = shard_tree(to_interleaved_storage(params, 2, 2), mesh,
                         param_specs(CFG))
    new_params, loss = step(sharded, toks)

    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
        new_params, to_interleaved_storage(ref_params, 2, 2))


def test_interleaved_step_matches_single_device():
    _run_isolated("_body_interleaved_step_matches_single_device")


def _body_interleaved_four_stages_ring_wrap():
    # P=4, v=2 (8 virtual stages over 8 layers), M=8: residual rings
    # and mailboxes wrap; loss must still match exactly.
    cfg = tf.tiny(remat=False, n_layers=8)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)))
    ref_loss = lm_loss(params, toks, cfg)
    mesh = make_mesh({"pp": 4, "tp": -1})
    step = make_pp_train_step(cfg, mesh, n_microbatches=8, lr=0.0,
                              schedule="interleaved", n_chunks=2)
    sharded = shard_tree(to_interleaved_storage(params, 4, 2), mesh,
                         param_specs(cfg))
    _, loss = step(sharded, toks)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)


def test_interleaved_four_stages_ring_wrap():
    _run_isolated("_body_interleaved_four_stages_ring_wrap")


def test_interleaved_bubble_shrinks_by_v():
    """The point of virtual stages: bubble *time* scales ~1/v. A slot
    in the v-chunk schedule costs 1/v of a v=1 slot (L/(P*v) layers),
    so compare slot counts divided by v."""
    P, M = 4, 8
    s1 = build_interleaved_schedule(P, 1, M)   # plain 1F1B timetable
    s2 = build_interleaved_schedule(P, 2, M)
    # Total wall-clock in stage-pass equivalents strictly improves.
    assert s2["T"] / 2 < s1["T"]
    # Worst-rank bubble time halves exactly at these sizes:
    # (P-1)*(tf+tb)/v with tf+tb = 2 slots/v.
    assert max(s1["bubbles"]) == 2 * (P - 1)
    assert max(s2["bubbles"]) == 2 * (P - 1)   # same slots, half the time
    assert max(s2["bubbles"]) / 2 < max(s1["bubbles"])


def test_interleaved_layer_order_round_robin():
    # L=8, P=2, v=2: rank 0's contiguous shard must hold model chunks
    # 0 and 2 (layers 0,1,4,5), rank 1 chunks 1 and 3 (layers 2,3,6,7).
    assert interleaved_layer_order(8, 2, 2) == [0, 1, 4, 5, 2, 3, 6, 7]


def test_interleaved_schedule_rejects_bad_m():
    with pytest.raises(ValueError, match="divisible"):
        build_interleaved_schedule(4, 2, 6)


def _body_1f1b_untied_embeddings():
    cfg = tf.tiny(remat=False, n_layers=4, tie_embeddings=False)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)))
    ref_params, ref_loss = sgd_train_step(params, toks, cfg, lr=0.1)
    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    step = make_pp_train_step(cfg, mesh, n_microbatches=2, lr=0.1,
                              schedule="1f1b")
    sharded = shard_tree(params, mesh, param_specs(cfg))
    new_params, loss = step(sharded, toks)
    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
        new_params, ref_params)


def test_1f1b_untied_embeddings():
    _run_isolated("_body_1f1b_untied_embeddings")


def _body_pp_adamw_matches_single_device():
    # AdamW through the 1F1B pipeline: moments shard with the params
    # (pp-local layer moments); step must match the single-device
    # AdamW step exactly.
    from tpushare.models.pipeline import make_pp_adamw_train_step
    from tpushare.models.training import adamw_init, adamw_train_step

    params, toks = _setup()
    ref_state = adamw_init(params)
    ref_params, ref_state, ref_loss = adamw_train_step(
        params, ref_state, toks, CFG, lr=1e-3, weight_decay=0.01)

    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    step = make_pp_adamw_train_step(CFG, mesh, n_microbatches=2,
                                    lr=1e-3, weight_decay=0.01,
                                    schedule="1f1b")
    from tpushare.models.training import opt_state_specs
    specs = param_specs(CFG)
    sharded = shard_tree(params, mesh, specs)
    state = shard_tree(adamw_init(params), mesh, opt_state_specs(specs))
    new_params, new_state, loss = step(sharded, state, toks)

    np.testing.assert_allclose(float(loss), float(ref_loss),
                               rtol=1e-5, atol=1e-6)
    # AdamW's g/sqrt(g^2) normalization turns bf16 grad rounding into
    # +-lr-scale step differences on near-zero grads, so params get a
    # looser atol than the SGD parity tests (observed: 1 elem/131k at
    # 3e-4 with everything else exact).
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-3),
        new_params, ref_params)
    for key in ("mu", "nu"):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-3),
            new_state[key], ref_state[key])
    assert int(new_state["count"]) == int(ref_state["count"]) == 1


def test_pp_adamw_matches_single_device():
    _run_isolated("_body_pp_adamw_matches_single_device")


def _body_pp_trainer_resume_bit_exact():
    # The preemption story end-to-end for pipeline training: a pp
    # tenant checkpoints (params + sharded AdamW moments + step),
    # "dies", and resumes — interrupted must equal uninterrupted
    # bit-exactly (trainer.fit drives any (params, opt, tokens) step,
    # so the pp AdamW step composes unchanged).
    import tempfile
    from tpushare.models import trainer
    from tpushare.models.pipeline import make_pp_adamw_train_step
    from tpushare.models.training import adamw_init, opt_state_specs

    params, _ = _setup()
    rng = np.random.default_rng(7)
    batches = [jnp.asarray(rng.integers(0, CFG.vocab_size, (4, 16)))
               for _ in range(4)]

    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    step = make_pp_adamw_train_step(CFG, mesh, n_microbatches=2,
                                    lr=1e-3, schedule="1f1b")
    specs = param_specs(CFG)
    p0 = shard_tree(params, mesh, specs)
    s0 = shard_tree(adamw_init(params), mesh, opt_state_specs(specs))

    # Uninterrupted: 4 steps straight.
    p_a, s_a, _ = trainer.fit(step, p0, s0, iter(batches), steps=4)

    # Interrupted: 2 steps, checkpoint, restore, 2 more.
    with tempfile.TemporaryDirectory() as d:
        ck = os.path.join(d, "ck")
        p_b, s_b, _ = trainer.fit(step, p0, s0, iter(batches[:2]), steps=2)
        trainer.save_state(ck, p_b, s_b, 2)
        p_r, s_r, start = trainer.load_state(
            ck, like_params=p_b, like_opt=s_b)
        assert start == 2
        p_c, s_c, _ = trainer.fit(step, p_r, s_r, iter(batches[2:]),
                                  steps=4, start_step=start)

    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), p_a, p_c)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        (s_a["mu"], s_a["nu"]), (s_c["mu"], s_c["nu"]))


def test_pp_trainer_resume_bit_exact():
    # This body runs ~12 collective executions (two fit paths plus a
    # checkpoint round-trip), so its per-run SIGABRT exposure is the
    # suite's highest — give it a deeper retry budget.
    _run_isolated("_body_pp_trainer_resume_bit_exact", attempts=5)


def _body_pp_sp_ring_attention_parity():
    # REAL sequence parallelism inside pipeline stages: tokens shard
    # over sp, blocks attend across shards via ring attention, and all
    # three schedules must still match the single-device step exactly
    # (pp x sp x tp composition — long-context pipeline training).
    from tpushare.models.pipeline import to_interleaved_storage
    params = tf.init_params(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (4, 33)))
    ref_params, ref_loss = sgd_train_step(params, toks, CFG, lr=0.1)

    mesh = make_mesh({"pp": 2, "sp": 2, "tp": 2})
    for sched in ("gpipe", "1f1b", "interleaved"):
        step = make_pp_train_step(CFG, mesh, n_microbatches=2, lr=0.1,
                                  schedule=sched)
        p = params if sched != "interleaved" else \
            to_interleaved_storage(params, 2, 2)
        r = ref_params if sched != "interleaved" else \
            to_interleaved_storage(ref_params, 2, 2)
        new_params, loss = step(shard_tree(p, mesh, param_specs(CFG)),
                                toks)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5, atol=1e-6, err_msg=sched)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                err_msg=sched),
            new_params, r)


def test_pp_sp_ring_attention_parity():
    _run_isolated("_body_pp_sp_ring_attention_parity")


def _body_pp_gemma2_style_windows_softcap():
    # Gemma-2-style alternating sliding windows + tanh softcap must
    # train identically through the pipeline and the single-device
    # path — on all three schedules, and composed with sp=2 ring
    # attention (windows cross shard boundaries).
    from tpushare.models.pipeline import to_interleaved_storage
    cfg = tf.tiny(remat=False, n_layers=4, sliding_window=8,
                  alternate_sliding=True, attn_softcap=30.0)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 33)))
    ref_params, ref_loss = sgd_train_step(params, toks, cfg, lr=0.1)

    mesh = make_mesh({"pp": 2, "sp": 2, "tp": 2})
    for sched in ("gpipe", "1f1b", "interleaved"):
        step = make_pp_train_step(cfg, mesh, n_microbatches=2, lr=0.1,
                                  schedule=sched)
        p = params if sched != "interleaved" else \
            to_interleaved_storage(params, 2, 2)
        r = ref_params if sched != "interleaved" else \
            to_interleaved_storage(ref_params, 2, 2)
        new_params, loss = step(shard_tree(p, mesh, param_specs(cfg)),
                                toks)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5, atol=1e-6, err_msg=sched)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                err_msg=sched),
            new_params, r)


def test_pp_gemma2_style_windows_softcap():
    _run_isolated("_body_pp_gemma2_style_windows_softcap")
