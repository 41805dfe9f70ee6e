"""The traffic generators: the copied trace generator against the
program's, and the chat mix's lengths against the seed."""
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from harness import chat, table2


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cores", [4, 8])
def test_table2_copy_equals_program(cores, monkeypatch):
    monkeypatch.setenv("SIM_TRACE_CACHE", "0")
    from repro.configs.ndp_sim import PRESETS, WORKLOADS
    from repro.workloads import generate_traces
    mix = _mix("sim.table2")
    length = _config("ndp-8c")["accesses_per_core"]
    full = PRESETS["full"]
    assert list(mix["workloads"]) == list(WORKLOADS)
    assert (length, mix["chunk"]) == (full.trace_len, full.chunk)
    want = generate_traces(list(WORKLOADS), cores, preset=full)
    got = table2.generate_suite(mix["workloads"], cores, length, full.seed)
    for w, g in zip(want, got):
        for k in ("vpn", "off", "work"):
            assert np.array_equal(w[k], g[k])
        assert w["pages"] == g["pages"]


def test_table2_seed_changes_traces():
    mix = _mix("sim.table2")
    a = table2.generate_suite(mix["workloads"], 2, 256, 1)
    b = table2.generate_suite(mix["workloads"], 2, 256, 2 ** 31 + 5)
    assert not np.array_equal(a[0]["vpn"], b[0]["vpn"])


def _first_lengths(mix, seed, rounds):
    out = []
    for c in chat.clients(mix, 92544, seed):
        for _ in range(rounds):
            prompt, n_out = c.next()
            out.append((len(prompt), n_out))
    return out


def test_chat_lengths_same_on_every_seed():
    mix = _mix("chat.closed8")
    rounds = 5
    base = sorted(_first_lengths(mix, 0, rounds))
    for seed in (1, 12345, 2 ** 31 + 7):
        assert sorted(_first_lengths(mix, seed, rounds)) == base
    table = chat.length_table(mix)
    assert set(base) <= set(table)
    # every context fits a slot of the configuration's deployment
    max_len = _config("internlm2-1_8b")["serving"]["max_len"]
    assert max(p + o for p, o in table) < max_len


def test_chat_seed_sets_order_and_tokens():
    mix = _mix("chat.closed8")
    a = [c.index for c in chat.clients(mix, 92544, 1)]
    b = [c.index for c in chat.clients(mix, 92544, 2)]
    assert sorted(a) == sorted(b) == list(range(mix["clients"]))
    pa = chat.clients(mix, 92544, 1)[0].next()[0]
    pb = chat.clients(mix, 92544, 2)[0].next()[0]
    assert not np.array_equal(pa[:8], pb[:8])


def test_chat_quantiles_are_log_normal():
    q = chat.quantile_lengths({"median": 1020, "sigma": 0.5,
                               "scale": 0.125}, 16)
    assert q == sorted(q)
    # the two middle quantiles straddle the scaled median, symmetrically
    # in the log
    assert q[7] < 127.5 < q[8]
    assert abs(np.log(q[7] / 127.5) + np.log(q[8] / 127.5)) < 0.02
    # the outermost bins' midpoints sit at z = +-1.863
    assert abs(np.log(q[-1] / q[0]) - 2 * 0.5 * 1.8627) < 0.02
