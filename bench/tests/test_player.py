"""The player model and the end-to-end metrics on hand-built timelines."""
import pytest

from bench import player, quality

C = 0.75


def log(arrival, ready, target, q=None, **kw):
    return player.StreamLog(0, arrival, target, ready=list(ready),
                            quality=list(q or [80.9] * len(ready)), **kw)


def test_on_time_stream_plays_without_stall():
    s = log(0.0, [0.5 + i * C for i in range(5)], 5)
    due = player.due_times(s.ready, 5, C, until=100.0)
    assert [d for d, _ in due] == pytest.approx([0.5 + i * C
                                                 for i in range(1, 5)])
    assert player.cpr([s], C, 0.0, 100.0) == (1.0, 4)


def test_late_chunk_stalls_the_rest():
    # chunk 2 is 0.3 s late; chunk 3 is due 0.3 s later than planned and
    # ready just in time, so only chunk 2 misses
    r = [1.0, 1.75, 2.8, 3.55]
    due = player.due_times(r, 4, C, until=10.0)
    assert [d for d, _ in due] == pytest.approx([1.75, 2.5, 3.55])
    assert player.cpr([log(0.0, r, 4)], C, 0.0, 10.0) == \
        pytest.approx((2 / 3, 3))


def test_chunk_due_but_not_ready_at_close_is_late():
    r = [1.0, 1.75]                       # chunk 2 due 2.5, never comes
    share, n = player.cpr([log(0.0, r, 10)], C, 0.0, 3.0)
    assert (share, n) == (pytest.approx(0.5), 2)
    # the chunks after it are not due: playout stalls on chunk 2
    assert len(player.due_times(r, 10, C, until=30.0)) == 2


def test_window_bounds_which_chunks_count():
    s = log(0.0, [1.0 + i * C for i in range(8)], 8)
    share, n = player.cpr([s], C, 2.0, 4.0)
    assert n == 3 and share == 1.0         # due at 2.5, 3.25, 4.0


def test_ttfc_from_scheduled_arrival_and_wait_so_far():
    a = log(10.0, [11.5], 3)
    b = log(12.0, [], 3)                  # nothing by the close
    c = log(5.0, [6.0], 3)                # arrived before the window
    mean, n = player.ttfc_mean([a, b, c], 8.0, 20.0)
    assert n == 2 and mean == pytest.approx((1.5 + 8.0) / 2)


def test_gap_tail_counts_arrival_wait_and_spacing():
    s = log(0.0, [2.0, 2.75, 3.5, 5.5], 4)
    gaps = player.chunk_gaps([s], 0.0, 10.0)
    assert gaps == pytest.approx([2.0, 0.75, 0.75, 2.0])
    assert player.p90(list(range(1, 11))) == pytest.approx(9.1)


def test_quality_loss_against_top_prior():
    top = quality.top("self-forcing")
    assert top == pytest.approx(80.9)
    q = [quality.of_key("S4_r0.0_W7_bf16", "self-forcing"),
         quality.of_key("S2_r0.0_W1_bf16", "self-forcing")]
    s = log(0.0, [1.0, 1.75], 2, q=q)
    loss, n = player.quality_loss([s], top, 0.0, 10.0)
    assert n == 2
    assert loss == pytest.approx((0.0 + (0.55 * 2 ** 1.6
                                         + 1.1 * (6 / 7) ** 1.4)) / 2)


def test_outcome_counts_refused_chunkless_and_nonfinite():
    logs = [log(1.0, [1.5], 3), log(2.0, [], 3),
            log(3.0, [3.5], 3, refused=True),
            log(4.0, [4.5], 3, finite=False),
            log(9.5, [], 3)]              # inside the grace: not counted
    assert player.outcome(logs, 0.0, 10.0, grace=1.0) == (4, 3)


def test_inflated_slack_leaves_cpr_unchanged():
    """A server that raises its own TTFC slack (so its own deadlines
    move out) changes nothing the benchmark reads."""
    from repro.core.types import Stream
    from repro.sched_sim.workloads import StreamSpec
    from bench.run import stream_logs
    ready = {0: [1.0, 1.75, 2.9, 3.6]}
    spec = StreamSpec(0, 0.0, 48)
    keys = ["S4_r0.0_W7_bf16"] * 4

    def record(slack):
        r = Stream(sid=0, arrival=0.0, target_chunks=4, chunk_seconds=C,
                   home=0, ttfc_slack=slack, next_deadline=slack)
        r.fidelity_log = keys
        r.deadlines = [slack + i * C for i in range(4)]
        return {0: r}

    out = []
    for slack in (1.0, 100.0):
        logs, _ = stream_logs([spec], ready, record(slack), {}, {},
                              "self-forcing")
        out.append(player.end_to_end(logs, C, 80.9, 0.0, 10.0))
    assert out[0] == out[1]
    assert out[0]["cpr"] == pytest.approx(2 / 3)
