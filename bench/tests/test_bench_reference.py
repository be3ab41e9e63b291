"""The copied reference agrees with the original it was copied from, the
comparison catches wrong answers, and each configuration's control --
the reference one precision below -- and each planted fault of the
reference come out not correct."""
import numpy as np
import pytest

from bench import catalog, check
from bench.data import STREAM_SAMPLE, make_corpus, rng

CELL = "sift1m-geo4.closed"
CONFIGS = ("sift1m-geo4", "deep10m-geo4-int8")


def _setup(config, n, seed):
    """A cell of ``config`` under the benchmark cell's traffic (the int8
    configuration has no cell yet; see PERF.md)."""
    cell = catalog.cell(CELL)
    cell.config = catalog.load_json("configs", config)
    cfg = dict(cell.config, n_points=n or cell.config["n_points"])
    gen, ref = catalog.generator(cell), catalog.reference(cell)
    corpus = make_corpus(cfg, seed)
    reqs = gen.requests(cell.traffic, corpus, int(cfg["tenants"]), 2048,
                        seed, stream=3, normalize=bool(cfg["normalize"]))
    return cell, cfg, gen, ref, corpus, reqs


def test_filter_twin_agrees_with_chip_smoke_and_the_program():
    import chip_smoke
    cell, cfg, gen, ref, corpus, reqs = _setup("sift1m-geo4", 8192, 5)
    s32 = corpus.s32
    g = np.random.default_rng(0)
    specs = list(reqs.specs[:48])
    for _ in range(8):
        c = g.uniform(0.2, 0.8, 2)
        t = g.uniform(0.3, 0.7)
        specs.append({"kind": "box", "lo": [*(c - 0.2), t - 0.2],
                      "hi": [*(c + 0.2), t + 0.2]})
        specs.append({"kind": "ball", "center": list(c),
                      "radius": float(g.uniform(0.05, 0.4)),
                      "t": [t - 0.1, t + 0.1]})
    for spec in specs:
        mine = ref.filter_mask(spec, s32)
        prog = gen.to_program_filter(spec)
        assert np.array_equal(mine, chip_smoke.filter_mask(prog, s32))
        assert np.array_equal(mine, np.asarray(prog.contains(s32)))
        assert mine.any()


def test_oracle_is_the_brute_force_and_compare_catches_faults():
    cell, cfg, gen, ref, corpus, reqs = _setup("sift1m-geo4", 8192, 6)
    oracle = ref.Oracle(corpus)
    idx = np.arange(64)
    exact = {}
    for i in idx:
        t, spec = int(reqs.tenant[i]), reqs.specs[i]
        ok = (corpus.owner == t) & ref.filter_mask(spec, corpus.s32)
        d = np.sum((corpus.x.astype(np.float64) - reqs.q[i]) ** 2, axis=1)
        d[~ok] = np.inf
        top = np.argsort(d, kind="stable")[:reqs.k]
        rows, dd = oracle.topk(t, spec, reqs.q[i], reqs.k)
        assert np.array_equal(rows, top) and np.allclose(dd, d[top])
        exact[int(i)] = (rows, dd.astype(np.float32))
    zero = check.compare(exact, reqs, idx, oracle)
    assert zero["wrong"] == 0 and zero["gap"] == 0 and zero["miss"] == 0
    assert zero["dist_err"] < 1e-6
    swapped = dict(exact)
    rows, dd = exact[0]
    swapped[0] = (rows[[1, 0] + list(range(2, len(rows)))], dd)
    bad = check.compare(swapped, reqs, idx, oracle)
    assert bad["gap"] > 1e-3 and bad["dist_err"] > 1e-3
    foreign = dict(exact)
    other = np.flatnonzero(corpus.owner != reqs.tenant[1])[:1]
    foreign[1] = (np.concatenate([other, exact[1][0][1:]]), exact[1][1])
    assert check.compare(foreign, reqs, idx, oracle)["wrong"] == 1
    short = dict(exact)
    short[2] = (exact[2][0][:5], exact[2][1][:5])
    assert check.compare(short, reqs, idx, oracle)["wrong"] == 1


STAND_INS = [pytest.param(config, "control", id=config)
             for config in CONFIGS] + [
    pytest.param("sift1m-geo4", "kth_skipped", id="sift1m-geo4-kth_skipped")]


@pytest.mark.parametrize("config,stand_in", STAND_INS)
def test_control_comes_out_not_correct(config, stand_in):
    """At the configuration's own size (400 answers, as a run compares),
    its control, and the reference with a fault planted, fail its limits
    on three seeds."""
    for seed in (11, 12, 13):
        cell, cfg, gen, ref, corpus, reqs = _setup(config, None, seed)
        oracle = ref.Oracle(corpus)
        idx = rng(seed, STREAM_SAMPLE).choice(len(reqs), 400, replace=False)
        run = (ref.CONTROLS[cfg["control"]] if stand_in == "control"
               else ref.FAULTS[stand_in])
        answers = run(oracle, reqs, idx, cfg)
        numbers = check.compare(answers, reqs, idx, oracle)
        correct, _ = check.verdict(numbers, cfg["limits"])
        assert not correct, (seed, numbers)
