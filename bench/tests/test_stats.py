from harness.stats import Record, window_metrics


def rec(rid, due, times, submitted=None, finished=True):
    return Record(rid=rid, due=due, submitted=due if submitted is None
                  else submitted, prompt_len=10, max_tokens=len(times),
                  token_times=list(times), tokens=[0] * len(times),
                  finished=finished)


def test_ttft_from_due_and_stalls_count_with_their_age():
    rs = [rec(0, 10.0, [10.5, 10.6]),
          rec(1, 11.0, [12.0], submitted=11.4),      # sent late
          rec(2, 19.0, [], finished=False),          # no token by the close
          rec(3, 5.0, [10.2, 10.3])]                 # due before the window
    m = window_metrics(rs, 10.0, 20.0)
    assert m["ttft_n"] == 3
    # 0.5, 1.0 (from due, not from sending) and 1.0 (age at the close)
    assert abs(m["ttft_p50_ms"] - 1000.0) < 1e-6
    assert abs(m["late_max_ms"] - 400.0) < 1e-6


def test_itl_counts_gaps_ending_in_window_and_open_gaps():
    rs = [rec(0, 0.0, [9.0, 10.5, 11.0, 12.0], finished=True),
          rec(1, 0.0, [15.0], finished=False)]
    m = window_metrics(rs, 10.0, 20.0)
    # 1.5, 0.5, 1.0 from request 0; the open gap 5.0 of request 1
    assert m["itl_n"] == 4
    assert abs(m["itl_p50_ms"] - 1250.0) < 1e-6


def test_output_tokens_over_the_window():
    rs = [rec(0, 0.0, [9.0, 10.5, 11.0, 12.0]), rec(1, 0.0, [19.9, 20.1])]
    m = window_metrics(rs, 10.0, 20.0)
    assert m["output_tok_s"] == 4 / 10.0
