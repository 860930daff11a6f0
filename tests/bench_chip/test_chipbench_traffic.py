"""The chip benchmark's traffic and fleet generator, and its refusal to
run without a chip."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

from chipbench import traffic  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
               if f.endswith(".json"))


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _stream(mix, seed, n, vocab=1000):
    t = traffic.Traffic(mix, traffic.tenant_names(mix), vocab, seed)
    return [t.next() for _ in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_stream_other_seed_differs(name):
    mix = _mix(name)
    big = 2 ** 31 + 12345
    a, b = _stream(mix, big, 50), _stream(mix, big, 50)
    for x, y in zip(a, b):
        assert (x.owner, x.max_new_tokens) == (y.owner, y.max_new_tokens)
        assert np.array_equal(x.prompt, y.prompt)
    c = _stream(mix, big + 1, 50)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    # seeds past 32 bits stay distinct
    d = _stream(mix, big + 2 ** 32, 50)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, d))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_in_range(name):
    mix = _mix(name)
    p, o = mix["prompt_len"], mix["output_len"]
    reqs = _stream(mix, 3, 300)
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"]
        assert r.prompt.min() >= 0 and r.prompt.max() < 1000
    lv = traffic.length_levels(p)
    assert len(lv) == p["levels"]
    assert lv == sorted(lv)
    # the deck is dealt whole: every level appears once per deck
    first = sorted(len(r.prompt) for r in reqs[:p["levels"]])
    assert first == lv


def test_lognormal_levels_median():
    lv = traffic.length_levels({"median": 256, "sigma": 0.7, "min": 64,
                                "max": 1024, "levels": 16})
    assert lv[7] < 256 < lv[8]
    assert lv[0] >= 64 and lv[-1] <= 1024


@pytest.mark.parametrize("name", MIXES)
def test_zipf_and_base_shares_hold(name):
    mix = _mix(name)
    fleet = mix["fleet"]
    deck = fleet["deck"]
    names = traffic.tenant_names(mix)
    reqs = _stream(mix, 11, 4 * deck)
    shares = traffic.zipf_shares(len(names), fleet["zipf_s"],
                                 fleet["base_share"])
    counts = [sum(r.owner is None for r in reqs)] + [
        sum(r.owner == n for r in reqs) for n in names]
    for want, got in zip(shares, counts):
        # four whole decks: each owner within one request per deck
        assert abs(got - want * 4 * deck) <= 4
    assert counts[1] > counts[2] > counts[-1]
    assert abs(shares[0] - fleet["base_share"]) < 1e-12


def test_owner_deck_counts_sum():
    d = traffic.owner_deck(8, 1.1, 0.1, 100)
    assert len(d) == 100
    assert d.count(-1) == 10


def _run(cmd, cwd, env):
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_harness_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _run([sys.executable, "benchmarks/chip/run.py", "--workload",
              "phi3m.decode-mixed", "--seed", "1", "--seconds", "1",
              "--trace", "0"], ROOT, env)
    assert r.returncode != 0
    assert "{" not in r.stdout
    assert "TPU" in r.stderr


def test_harness_needs_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = _run([sys.executable, "benchmarks/chip/run.py", "--workload",
              "phi3m.decode-mixed", "--seed", "1", "--seconds", "1",
              "--trace", "0"], str(tmp_path), env)
    assert r.returncode != 0
    assert "{" not in r.stdout
