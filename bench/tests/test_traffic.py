import numpy as np

from harness import traffic

SPEC = {"loop": "closed", "clients": 4, "fill": "residual", "requests": 64,
        "prompt": {"median": 4096, "sigma": 0.35, "min": 2048, "max": 6144},
        "output": {"median": 1024, "sigma": 0.5, "min": 256, "max": 2048}}
OPEN = dict(SPEC, loop="open", rate_per_s=4.0)
BIG = 2 ** 31 + 12345


def test_same_seed_same_inputs():
    a, b = traffic.Mix(SPEC, BIG, 1000), traffic.Mix(SPEC, BIG, 1000)
    for _ in range(5):
        x, y = a.next_item(0.3), b.next_item(0.3)
        assert x.max_tokens == y.max_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_every_seed_gets_the_same_sizes_in_another_order():
    a, b = traffic.Mix(SPEC, 1, 1000), traffic.Mix(SPEC, 2, 1000)
    assert sorted(a.prompt_lens) == sorted(b.prompt_lens)
    assert sorted(a.output_lens) == sorted(b.output_lens)
    assert list(a.prompt_lens) != list(b.prompt_lens)
    assert set(traffic.lognormal_sizes(64, SPEC["prompt"])) <= set(
        range(2048, 6145))


def test_lognormal_quantiles():
    sizes = traffic.lognormal_sizes(1001, {"median": 1000, "sigma": 0.5,
                                          "min": 1, "max": 10 ** 6})
    assert sizes[500] == 1000
    # the 84th percentile of a lognormal lies sigma above the median
    assert abs(sizes[841] / 1000 - np.exp(0.5)) < 0.01


def test_residual_fill_moves_output_into_prompt():
    m = traffic.Mix(SPEC, 3, 1000)
    p, o = int(m.prompt_lens[0]), int(m.output_lens[0])
    it = m.next_item(0.5)
    done = int(0.5 * (o - 1))
    assert len(it.prompt) == p + done and it.max_tokens == o - done


def test_open_loop_arrivals_are_fixed_gaps_reordered():
    a, b = traffic.Mix(OPEN, 1, 100), traffic.Mix(OPEN, 2, 100)
    assert np.allclose(sorted(a.gaps), sorted(b.gaps))
    assert abs(a.gaps.mean() - 0.25) < 0.01
    t = a.arrival_offsets(40.0)
    assert np.all(np.diff(t) > 0) and t[-1] < 40.0
    assert abs(len(t) - 160) < 8
