"""The traffic generator: one seed gives the same schedule twice, and every
seed gets the same work in another order."""
from collections import Counter

import numpy as np
import pytest

from perfbench import core, traffic


def spec(name):
    """A traffic file; ``steady-1024t`` and ``backlog-48t`` are the
    serving mix at 1024 tenants and in a closed loop."""
    base = core.load_json(core.BENCH_DIR / "traffic" / "steady-48t.json")
    if name == "steady-1024t":
        return dict(base, tenants=1024)
    if name == "backlog-48t":
        return dict(base, kind="closed_loop", outstanding_per_tenant=4,
                    requests_per_tenant=64)
    return core.load_json(core.BENCH_DIR / "traffic" / f"{name}.json")


def schedule(tr):
    return [(a.rid, a.tenant, a.prompt_len, a.max_new, a.due_s)
            for a in tr.arrivals]


@pytest.mark.parametrize("name", ["steady-48t", "steady-1024t"])
def test_one_seed_gives_the_same_schedule_twice(name):
    a = traffic.serving(spec(name), 2 ** 40 + 7, 51.0)
    b = traffic.serving(spec(name), 2 ** 40 + 7, 51.0)
    assert schedule(a) == schedule(b)
    assert a.weight_mb == b.weight_mb


def test_seeds_deal_the_same_work_to_other_tenants():
    s = spec("steady-48t")
    a = traffic.serving(s, 1, 51.0)
    b = traffic.serving(s, 2, 51.0)
    work = lambda tr: Counter((x.due_s, x.prompt_len, x.max_new)  # noqa: E731
                              for x in tr.arrivals)
    assert work(a) == work(b)
    assert [x.tenant for x in a.arrivals] != [x.tenant for x in b.arrivals]
    assert sorted(a.weight_mb) == sorted(b.weight_mb)


def test_open_loop_mix_matches_the_serving_generator():
    s = spec("steady-48t")
    tr = traffic.serving(s, 3, 200.0)
    n = len(tr.arrivals)
    # MMPP keeps the mean rate: about rate x seconds requests
    rate = s["rate_per_s"]
    assert 0.6 * rate * 200 < n < 1.4 * rate * 200
    assert all(64 <= a.prompt_len < 512 and 16 <= a.max_new < 128
               for a in tr.arrivals)
    assert all(0 <= a.due_s < 200.0 for a in tr.arrivals)
    dues = [a.due_s for a in tr.arrivals]
    assert dues == sorted(dues)
    rates = traffic.tenant_rates(s)
    assert rates.sum() == pytest.approx(rate)
    assert rates[-1] / rates[0] == pytest.approx(10 ** 1.8)


def test_closed_loop_sizes_per_tenant():
    s = spec("backlog-48t")
    a = traffic.serving(s, 11, 51.0)
    b = traffic.serving(s, 11, 51.0)
    assert a.sizes == b.sizes and a.outstanding == 4
    assert sorted(a.sizes) == list(range(48))
    assert all(len(v) == 64 for v in a.sizes.values())


def test_mmpp_copy_keeps_its_mean_rate():
    rng = np.random.default_rng(0)
    n = sum(len(traffic.mmpp_arrivals(2.0, 100.0, rng, 1.0, 9.0))
            for _ in range(40))
    assert 0.85 * 8000 < n < 1.15 * 8000

