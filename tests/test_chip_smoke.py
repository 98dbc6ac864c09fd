"""``chip_smoke.py`` on the CPU at a tiny size, so chip time is never
spent on a Python error: part A's builders, its NumPy reference and its
pass condition (kill, recover, reference equality), part B's sequence and
audit check, and the refusal to run without a TPU."""

import numpy as np
import pytest

import chip_smoke


def test_reference_fold_by_hand():
    """Two source subtasks, one record per step, windows of two steps:
    batch s counts into window (s+1)//2; window w's rows are stamped
    2(w+1) and carry the key's running sum."""
    feed = np.array([[[0, 5], [0, 7], [1, 2], [0, 1], [0, 9], [1, 4]],
                     [[1, 3], [0, 1], [1, 8], [1, 6], [0, 2], [0, 3]]],
                    np.int32)
    # windows: w0 <- step 0; w1 <- steps 1, 2; w2 <- steps 3, 4.
    # 9 steps run: windows with 2(w+1) + 2 <= 8 are visible -> w0..w2.
    got = chip_smoke.reference_committed(feed, batch=1, window_steps=2,
                                         steps_run=9)
    want = np.array([[0, 5, 2], [0, 13, 4], [0, 25, 6],
                     [1, 3, 2], [1, 13, 4], [1, 19, 6]], np.int32)
    np.testing.assert_array_equal(got, want)
    # One step fewer and window 2's rows have not reached the sink.
    got8 = chip_smoke.reference_committed(feed, 1, 2, steps_run=8)
    np.testing.assert_array_equal(got8, want[[0, 1, 3, 4]])


def test_part_a_tiny_kill_recover_matches_reference(tmp_path):
    shape = chip_smoke.ServedShape(
        parallelism=4, batch=4, num_keys=13, edge_capacity=16,
        steps_per_epoch=16, window_steps=4, kill_after=8)
    feed = chip_smoke.make_feed(shape, seed=3)
    res = chip_smoke.run_served(shape, feed, str(tmp_path / "ck"), seed=3)
    rep = res["report"]
    assert len(rep.failed_subtasks) == 3 and rep.steps_replayed == 8
    chip_smoke.check_served(shape, feed, res)
    # The check has teeth: a lost and a duplicated record both fail it.
    for bad in (res["committed"][1:],
                np.concatenate([res["committed"], res["committed"][:1]])):
        with pytest.raises(AssertionError, match="NumPy reference"):
            chip_smoke.check_served(shape, feed, dict(res, committed=bad))


def test_part_b_tiny_kill_recover_audited():
    res = chip_smoke.run_headline(spe=16, fill=4, block_steps=8,
                                  recovery_block_steps=32)
    rep = res["report"]
    assert rep.failed_subtasks == (chip_smoke.HEADLINE_PAR + 1,)
    assert rep.steps_replayed == 32 and rep.from_epoch == 1
    chip_smoke.check_audit(res["runner"], rep, min_validated=2)
    assert chip_smoke.job_counter(res["runner"],
                                  "recovery.aot-lower-failed") == 0


def test_part_j_tiny_window_join_block_form_against_step_form():
    # 600 ids over 2 subtasks: 384 own columns each, 294 and 306 bound
    assert chip_smoke.check_window_join(21, K=20, P=2, B=32,
                                        num_keys=600) > 100


def test_part_j_tiny_window_join_in_wide_blocks_against_narrow_ones():
    assert chip_smoke.check_window_join_in_a_job(21, spe=128,
                                                 epochs=6) > 200


def test_part_s_tiny_sessions_in_wide_blocks_against_narrow_ones():
    assert chip_smoke.check_sessions_in_a_job(21, spe=128, epochs=5) > 200


def test_part_s_tiny_sessions_by_head_and_tails_against_every_slot():
    # 8 receive windows of 512 slots: the split is built, the hot
    # bidder's owner is past the head every step
    assert chip_smoke.check_sessions_in_a_job(21, spe=128, epochs=3, p=8,
                                              batch=64) > 100


def test_part_s_tiny_sessions_of_three_hot_bidders_take_the_dense_branch():
    # three targets past the head in one step: the block programs hold
    # the cond and its dense branch runs (the check raises if it does not)
    assert chip_smoke.check_sessions_in_a_job(21, spe=128, epochs=3, p=8,
                                              batch=64, hot=3) > 100


def test_part_i_tiny_incremental_join_in_wide_blocks_against_narrow_ones():
    rows, flushed, stepped = chip_smoke.check_incremental_join_in_a_job(
        21, spe=128, epochs=6)
    assert rows > 1000 and flushed > 100 and stepped > 0


def test_part_q_tiny_best_in_interval_in_wide_blocks_against_narrow_ones():
    rows, won, valid, stepped = chip_smoke.check_best_in_interval_in_a_job(
        21, spe=128, epochs=6)
    assert rows > 100 and won > 500 and valid > won


def test_part_u_tiny_union_by_rank_against_the_step_forms_scan():
    # the tiny stand-in's union (64 + 448 slots into 64), a third run of
    # the job with the keyed-state mapper's read-back by a gather (PR 52:
    # each run's block program is checked for the form it traced), then
    # 48 + 32 records a subtask a step into 80
    rows, kept, sent = chip_smoke.check_union_in_a_job(21, spe=128,
                                                       epochs=4, tiny=True)
    assert rows > 512 and sent // 2 < kept < sent


def test_part_x_tiny_cascade_on_a_mesh_of_four(tmp_path):
    # nexmark-q3-x4's stand-in: 8 subtasks a vertex over four forced
    # host devices, the default sharing depth, the cell's own kill
    x = chip_smoke.check_cascade_on_the_mesh(
        21, str(tmp_path / "ck"), config="tiny-nexmark-q3-x4", tiny=True)
    assert (x["victims"], x["fetch_hops"], x["epochs"]) == (4, 1, 5)
    assert x["rows"] > 500 and x["fullest_gib"] < 0.26 * x["carry_gib"]


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err
