"""The traffic generator: seeded, and what share of requests re-plan."""
import json

import numpy as np
import pytest

from perfbench import core, traffic
from perfbench.reference.allocation import QuasiDynamicRule

SPEC = core.load_spec()
CONFIGS = {c["name"]: json.loads((core.ROOT / c["file"]).read_text()) for c in SPEC["configs"]}
MIXES = sorted({w["traffic"] for w in SPEC["workloads"]})


def _params(mix):
    return json.loads((core.BENCH_DIR / "traffic" / f"{mix}.json").read_text())


def _stream(cfg, mix, seed):
    return traffic.RequestStream(_params(mix), [a["lam"] for a in cfg["apps"]],
                                 cfg["caps"]["r_cpu"], cfg["caps"]["r_mem"], seed)


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_same_seed_same_trace_other_seed_other_trace(config, mix):
    cfg = CONFIGS[config]
    a, b = _stream(cfg, mix, 2**31 + 7), _stream(cfg, mix, 2**31 + 7)
    n = 3 * a.rates.shape[0]
    for j in range(n):
        assert np.array_equal(a[j].lam, b[j].lam) and a[j].r_cpu == b[j].r_cpu
    others = [_stream(cfg, mix, 2**31 + k) for k in range(8, 40)]
    if "rates_seed" in _params(mix):
        # the same cycle of rates for every seed, entered at another epoch
        assert all(np.array_equal(o.rates, a.rates) for o in others)
        assert len({o.start for o in others}) > 1
        other = next(o for o in others if o.start != a.start)
        assert not np.array_equal(other[0].lam, a[0].lam)
    else:
        other = others[0]
        assert not np.array_equal(other.rates, a.rates)
    # the seed moves the apps' phases, not the swing: every app of every
    # seed peaks within the same band around its base rate
    base = np.array([x["lam"] for x in cfg["apps"]])
    for s in (a, other):
        ratio = s.rates / base
        assert np.all(ratio > 0.6) and np.all(ratio < 1.45)
        assert np.all(ratio.max(axis=0) > 1.05) and np.all(ratio.min(axis=0) < 0.95)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_share_of_requests_that_replan(config):
    cfg = CONFIGS[config]
    params = _params("replan_drift")
    stream = _stream(cfg, "replan_drift", 12345)
    rule = QuasiDynamicRule(params["threshold"])
    n = 10 * params["cycle_epochs"]
    replan = cold = 0
    caps = None
    for j in range(n):
        req = stream[j]
        did, _ = rule.observe(req.lam, req.r_cpu, req.r_mem)
        replan += did
        cold += (req.r_cpu, req.r_mem) != caps
        caps = (req.r_cpu, req.r_mem)
    print(f"{config}: of {n} requests {replan / n:.1%} re-plan, at least {cold / n:.1%} "
          f"cold (first request and cap resizes), {1 - replan / n:.1%} skipped")
    assert 0 < cold < replan <= n
    assert np.all(stream.rates > 0)
