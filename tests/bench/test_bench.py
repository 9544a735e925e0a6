"""The serving benchmark's own tests, on the CPU.

* the reduction from a trace extract to per-layer metrics: the union of
  device intervals, per-kernel and per-program sums, and shares of a peak
  that stay at or under 100% on a recorded chip trace;
* the byte and operation counts, and the traffic generator;
* ``BENCHMARK.json`` against the benchmark's rules;
* the dense GQA weight draw: untied, what it drew before configurations
  named their architecture module; tied, at the head's scale;
* whole runs at a tiny size (the chip check skipped): a configuration,
  traffic mix and metric added as files alone, and an architecture module
  and reference added as files alone; the program passes the correctness
  limit and the fp8 control fails it, with an untied and a tied head;
  every fault the cells can have, planted under the timed path, makes
  ``correct`` false;
* ``bench/run.py`` exits non-zero with no result off the chip, and without
  the program beside it.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import counts, model, trace  # noqa: E402
from bench.traffic import generate, load_traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load_bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("intervals,union", [
    ([], 0.0),
    ([(0, 10)], 10.0),
    ([(0, 10), (5, 10)], 15.0),            # overlap
    ([(0, 10), (2, 3)], 10.0),             # nested
    ([(20, 5), (0, 10)], 15.0),            # disjoint, unsorted
    ([(0, 10), (10, 5)], 15.0),            # touching
])
def test_union_of_intervals(intervals, union):
    assert trace.union_ns(intervals) == union


def small_extract():
    # two chips, 100 ns window; chip 0 busy 0-30 and 50-70 (overlapping
    # ops), chip 1 busy 0-50
    return {"window_ns": 100.0, "chips": 2, "ops": [
        ["fusion.1", "m", 0.0, 30.0, 0],
        ["custom-call.2 sals_latent_topk", "m", 50.0, 10.0, 0],
        ["custom-call.3 sals_recon_attend", "m", 55.0, 15.0, 0],
        ["fusion.4", "m", 0.0, 50.0, 1]],
        "modules": [["jit__decode_impl(1)", 0.0, 70.0, 0],
                    ["jit__prefill_chunk_impl(2)", 0.0, 20.0, 1],
                    ["jit__decode_impl(1)", 80.0, 10.0, 0]],
        "host": [["bench.decode_call", 28.0, 30.0]]}


def test_busy_idle_and_sums_on_a_small_extract():
    ex = small_extract()
    assert trace.busy_ns(ex) == (50.0 + 50.0) / 2
    assert trace.kernel_ns(ex, "sals_latent_topk") == (10.0, 1)
    assert trace.kernel_ns(ex, "sals_recon_attend") == (15.0, 1)
    assert trace.module_ns(ex, "_decode_impl") == (80.0, 2)
    gaps = trace.idle_gaps(ex, chip=0)
    assert sorted((s, d) for s, d, _ in gaps) == [(30.0, 20.0), (70.0, 30.0)]
    names = {s: n for s, _, n in gaps}
    assert names[30.0] == "bench.decode_call" and names[70.0] == "host"
    bd = trace.breakdown(ex)
    assert bd["device_ops"][0][0] == "fusion.4"
    assert bd["device_ops"][0][1] == pytest.approx(50e-9)
    assert len(bd["idle_gaps"]) == 2
    # a loop op spanning its body: busy time unchanged, not a leaf
    ex["ops"].append(["%while.7", "m", 50.0, 20.0, 0])
    assert trace.busy_ns(ex) == 50.0
    assert "%while.7" not in [o[0] for o in trace.leaves(ex["ops"])]
    assert not any(k.startswith("%while")
               for k, _ in trace.breakdown(ex)["device_ops"])


def read_metric(name, ctx):
    from bench import harness
    return harness._read_metric(name, ctx)


def arch_module(cfg):
    """The architecture module a configuration names."""
    return model.load_module(cfg["architecture"])


def recording_context(path):
    from bench import harness
    with open(path) as f:
        rec = json.load(f)
    cfg = model.load_config(rec["config"])
    am = arch_module(cfg)
    steps = [tuple(s) for s in rec["steps"]]
    return harness.Context(
        recs=[], extract=rec["extract"], steps=steps,
        tr0=min(s[0] for s in steps), tr1=max(s[0] for s in steps),
        arch=am.Arch(cfg), traffic=load_traffic(rec["traffic"]),
        counts=am.counts,
        trace=trace, peaks=model.load_json("peaks.json"),
        device_kind=rec["device_kind"])


RECORDINGS = sorted(f for f in os.listdir(DATA) if f.endswith(".trace.json"))


@pytest.mark.parametrize("name", RECORDINGS)
def test_recorded_chip_trace_shares_stay_under_peak(name):
    ctx = recording_context(os.path.join(DATA, name))
    ex = ctx.extract
    busy = trace.busy_ns(ex)
    assert 0 < busy <= ex["window_ns"]
    bench = load_bench()
    shares = [m["name"] for m in bench["per_layer"] if m["unit"] == "%"]
    read = {}
    for m in shares:
        v = read_metric(m, ctx)
        if v is not None:
            read[m] = v
            assert 0.0 <= v <= 100.0, (m, v)
    assert read, "no share could be read from the recording"
    step = read_metric("decode_step_ms.decode", ctx)
    if step is not None:
        ns, runs = trace.module_ns(ex, "_decode_impl")
        assert runs > 0 and step == pytest.approx(ns / runs * 1e-6)


def test_unknown_device_is_an_error():
    from bench import harness, shares
    ctx = harness.Context(peaks=model.load_json("peaks.json"),
                          device_kind="not a chip")
    with pytest.raises(KeyError):
        shares.peaks(ctx)


# ---------------------------------------------------------------------------
# counts and traffic
# ---------------------------------------------------------------------------

def tiny_config(name="tiny-gqa"):
    with open(os.path.join(DATA, "configs", f"{name}.json")) as f:
        return json.load(f)


def tiny_arch():
    cfg = tiny_config()
    return arch_module(cfg).Arch(cfg)


def test_counts_by_hand():
    a = tiny_arch()
    # d 64, 4/2 heads of 16, ff 128, 4 layers (1 SALS), rank 8, r* 8
    assert (a.kvd, a.rank, a.r_star, a.n_sals, a.n_full) == (32, 8, 8, 1, 3)
    layer = 64 * (64 + 2 * 32) + 64 * 64 + 3 * 64 * 128
    assert a.matmul_params() == 4 * layer + 64 * 256
    live, nc = 100, 16
    # selected: 16 of the 100 - 4 - 8 selectable
    assert counts.selected(a, nc, live) == 16
    assert counts.selected(a, nc, 20) == 8
    ops, b = counts.latent_topk(a, live)
    assert ops == 2 * 8 * 100 and b == 100 * 8 * 2 + 8 * 4
    ops, b = counts.recon_attend(a, nc, live)
    assert ops == 2 * 16 * 8 * 32 + 4 * 4 * 16 * 16
    assert b == 16 * (8 * 2 + 32 + 2 * 2 * 32 / 16) + 64 * 2
    assert counts.recon_shared_bytes(a) == 32 * 8 * 2
    f = counts.decode_flops(a, nc, [live])
    expect = 2 * a.matmul_params() + 3 * 4 * 4 * 16 * live + (
        2 * 32 * 8 + 2 * 32 * 8 + 2 * 8 * 100
        + 2 * 16 * 8 * 32 + 4 * 4 * 16 * 16 + 4 * 4 * 16 * 12)
    assert f == expect
    assert counts.decode_flops(a, nc, [live, live]) == 2 * f
    # U_r is read once per SALS layer and step, however many rows
    one, two = (counts.decode_bytes(a, nc, [live] * k) for k in (1, 2))
    row = 64 * 2 + 3 * live * 2 * 32 * 2 + (
        counts.latent_topk(a, live)[1] + counts.recon_attend(a, nc, live)[1]
        + 12 * 2 * 32 * 2)
    assert two - one == row
    assert one == (a.param_count() - 256 * 64) * 2 + 32 * 8 * 2 + row


@pytest.mark.parametrize("traffic", ["decode32k_b4", "chat4k"])
def test_seeds_share_sizes_not_order(traffic):
    t = load_traffic(traffic)
    a = generate(t, 32000, 2 ** 31 + 12345, 30.0)
    b = generate(t, 32000, 7, 30.0)
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new_tokens for p in a) == \
        sorted(p.max_new_tokens for p in b)
    if t["kind"] == "open_loop":
        gaps = lambda plan: sorted(np.round(np.diff(  # noqa: E731
            [p.due_s for p in plan]), 9))
        assert gaps(a) == gaps(b)
        assert min(p.due_s for p in a) == -t["lead_in_s"]
        # each seed orders the sizes and the arrivals its own way
        assert [(p.due_s, len(p.prompt), p.max_new_tokens) for p in a] != \
            [(p.due_s, len(p.prompt), p.max_new_tokens) for p in b]
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b] or \
        not np.array_equal(a[0].prompt, b[0].prompt)
    again = generate(t, 32000, 2 ** 31 + 12345, 30.0)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))


# ---------------------------------------------------------------------------
# BENCHMARK.json
# ---------------------------------------------------------------------------

def test_benchmark_json_follows_the_rules():
    b = load_bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    for p in b["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in b["command"]:
        assert not word.startswith("/") and ".." not in word
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        f = os.path.join(ROOT, c["file"])
        assert os.path.isfile(f)
        cfg = model.load_config(c["name"])
        assert cfg["reduced"] == c["reduced"]
        for key in ("architecture", "reference"):   # files of the benchmark
            assert os.path.isfile(os.path.join(ROOT, cfg[key])), cfg[key]
            assert any(cfg[key].startswith(p + "/") for p in b["paths"])
    cells = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
        load_traffic(w["traffic"])
        lim = model.load_json("limits", f"{w['name']}.json")
        assert lim["max_logit_gap"] > 0
    names = [w["name"] for w in b["workloads"]]
    assert {w["config"] for w in b["workloads"]} == set(configs)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.isfile(model.path("metrics", f"{m['name']}.py"))
        assert set(m.get("workloads", [])) <= set(names)
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in b["per_layer"]:
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        for w in m.get("workloads", names):
            reports = e2e[m["moves"]].get("workloads", names)
            assert w in reports, (m["name"], w)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"], 0)
    for w in names:                       # every cell: set-up, one more
        own = [m for m in b["end_to_end"] if w in m.get("workloads", names)]
        assert len(own) >= 2
        assert any(w in m.get("workloads", names) for m in b["per_layer"])


# ---------------------------------------------------------------------------
# the dense GQA weight draw
# ---------------------------------------------------------------------------

# sha256 of make_weights and make_projector at tiny-gqa, seed 2**31 + 7,
# recorded on the commit before configurations named their architecture
# module (4b68c7c): untied configurations draw what they drew then
UNTIED_DRAW_SHA256 = \
    "121ca451bd0be3647d8591b1569994b805b7e384881a7eff3ea87862594760fc"


def draw_digest(cfg, seed):
    import jax
    am = arch_module(cfg)
    arch = am.Arch(cfg)
    w = am.make_weights(arch, seed)
    h = hashlib.sha256()
    for tree in (w, am.make_projector(arch, w)):
        for k in sorted(tree):
            a = np.asarray(jax.device_get(tree[k]))
            h.update(k.encode())
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
    return h.hexdigest(), w


def test_untied_draw_is_what_it_was():
    assert draw_digest(tiny_config(), 2 ** 31 + 7)[0] == UNTIED_DRAW_SHA256


def test_tied_embedding_is_drawn_at_the_heads_scale():
    untied, tied = tiny_config(), tiny_config("tiny-gqa-tied")
    assert {k for k in set(untied) | set(tied) if untied.get(k) !=
            tied.get(k)} == {"name", "source", "tie_word_embeddings"}
    _, wu = draw_digest(untied, 5)
    _, wt = draw_digest(tied, 5)
    assert "head" in wu and "head" not in wt
    std = lambda w: float(np.std(np.asarray(w["embed"], np.float32)))  # noqa: E731
    d = untied["hidden_size"]
    assert std(wu) == pytest.approx(1.0, rel=0.05)
    assert std(wt) == pytest.approx(d ** -0.5, rel=0.05)


# ---------------------------------------------------------------------------
# whole runs at a tiny size
# ---------------------------------------------------------------------------

@pytest.fixture
def tiny_bench(tmp_path, monkeypatch):
    """A copy of ``bench/`` with the tiny configuration, traffic and limits
    added as files, and a BENCHMARK dict with the tiny cells."""
    here = tmp_path / "bench"
    shutil.copytree(model.HERE, here)
    for sub in ("configs", "traffic", "limits"):
        for f in os.listdir(os.path.join(DATA, sub)):
            shutil.copy(os.path.join(DATA, sub, f), here / sub / f)
    monkeypatch.setattr(model, "HERE", str(here))
    b = load_bench()
    kind = lambda w: load_traffic(w["traffic"])["kind"]  # noqa: E731
    real = {w["name"]: kind(w) for w in b["workloads"]}
    b["workloads"] = [
        {"name": f"tiny-gqa.{k}", "config": "tiny-gqa", "traffic": f"tiny-{k}",
         "chips": 1, "why": "test"} for k in ("resident", "chat")] + [
        {"name": "tiny-gqa-tied.resident", "config": "tiny-gqa-tied",
         "traffic": "tiny-resident", "chips": 1, "why": "test"}]
    # a metric of some cells goes to the tiny cells of the same traffic kind
    b["end_to_end"] = [
        dict(m, workloads=[w["name"] for w in b["workloads"] if kind(w) in
                           {real[n] for n in m["workloads"]}])
        if "workloads" in m else m for m in b["end_to_end"]]
    b["per_layer"] = []
    return here, b


def tiny_run(b, cell, seed, seconds=0.5, **kw):
    from bench import harness
    return harness.run(cell, seed, seconds, False, time.perf_counter(),
                       require_chip=False, bench=b, **kw)


def test_new_config_traffic_and_metric_are_files_alone(tiny_bench):
    here, b = tiny_bench
    cfg = json.load(open(here / "configs" / "tiny-gqa.json"))
    cfg.update(name="made-up", num_hidden_layers=5)
    (here / "configs" / "made-up.json").write_text(json.dumps(cfg))
    tr = json.load(open(here / "traffic" / "tiny-chat.json"))
    tr.update(rate_per_s=5.0)
    (here / "traffic" / "made-up-chat.json").write_text(json.dumps(tr))
    (here / "metrics" / "made_up_tokens.py").write_text(
        "def read(ctx):\n    return float(ctx.window_tokens())\n")
    (here / "limits" / "made-up.made-up-chat.json").write_text(
        json.dumps({"max_logit_gap": 10.0}))
    b["workloads"].append({"name": "made-up.made-up-chat",
                           "config": "made-up", "traffic": "made-up-chat",
                           "chips": 1, "why": "added as files"})
    for m in b["end_to_end"]:         # the cell joins the chat cells' tail
        if "tiny-gqa.chat" in m.get("workloads", []):
            m["workloads"].append("made-up.made-up-chat")
    b["end_to_end"].append({"name": "made_up_tokens", "unit": "tokens",
                            "better": "higher", "bound": 0.1,
                            "source": "host_clock"})
    res = tiny_run(b, "made-up.made-up-chat", 3, seconds=1.0)
    assert res["metrics"]["made_up_tokens"]["value"] > 0
    assert set(res["metrics"]) == {"itl_p99_ms", "setup_s", "made_up_tokens"}
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 3 and res["failed"] == 0


def logged_copy(base, names, log):
    """A module that stands in for ``base`` under another name and logs
    each of ``names`` that is called."""
    lines = ['"""Added as a file: %s under another name."""' % base,
             "from bench.model import load_module",
             "",
             f"_base = load_module({base!r})",
             "counts = getattr(_base, 'counts', None)",
             "",
             "",
             "def _logged(name):",
             "    def call(*a, **kw):",
             f"        with open({str(log)!r}, 'a') as f:",
             "            f.write(name + '\\n')",
             "        return getattr(_base, name)(*a, **kw)",
             "    return call",
             ""]
    return "\n".join(lines + [f"{n} = _logged({n!r})" for n in names]) + "\n"


def test_new_architecture_and_reference_are_files_alone(tiny_bench,
                                                        tmp_path):
    here, b = tiny_bench
    log = tmp_path / "calls.log"
    arch_calls = ["Arch", "make_weights", "make_projector", "program_params",
                  "program_configs"]
    ref_calls = ["hidden", "head_stats"]
    (here / "archs" / "made_up_gqa.py").write_text(
        logged_copy("bench/archs/dense_gqa.py", arch_calls, log))
    (here / "reference" / "made_up_gqa.py").write_text(
        logged_copy("bench/reference/dense_gqa.py", ref_calls, log))
    cfg = tiny_config()
    cfg.update(name="made-up-arch", architecture="bench/archs/made_up_gqa.py",
               reference="bench/reference/made_up_gqa.py")
    (here / "configs" / "made-up-arch.json").write_text(json.dumps(cfg))
    shutil.copy(here / "limits" / "tiny-gqa.resident.json",
                here / "limits" / "made-up-arch.resident.json")
    b["workloads"].append({"name": "made-up-arch.resident",
                           "config": "made-up-arch",
                           "traffic": "tiny-resident", "chips": 1,
                           "why": "an architecture added as files"})
    res = tiny_run(b, "made-up-arch.resident", 2 ** 31 + 99)
    assert res["correct"], res["checks"]
    assert set(log.read_text().split()) == set(arch_calls + ref_calls)
    for f in ("harness.py", "model.py"):     # neither was edited
        assert (here / f).read_text() == \
            open(os.path.join(ROOT, "bench", f)).read()


@pytest.mark.parametrize("cell", ["tiny-gqa.resident",
                                  "tiny-gqa-tied.resident"])
def test_program_passes_and_fp8_control_fails(tiny_bench, cell):
    _, b = tiny_bench
    res = tiny_run(b, cell, 2 ** 31 + 99, control=True)
    gap, ctl = res["checks"]["max_logit_gap"], res["checks"]["control_gap"]
    # the control stands in the program's place, so the run is not correct
    assert not res["correct"], res["checks"]
    assert gap["value"] <= gap["limit"] < ctl["value"], res["checks"]
    assert tiny_run(b, cell, 2 ** 31 + 99)["correct"]
    assert list(res)[-2:] == ["checks", "_recording"]
    assert set(res["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}


FAULT_CELLS = {"resident": "tiny-gqa.resident", "chat": "tiny-gqa.chat",
               "tied-resident": "tiny-gqa-tied.resident"}


@pytest.mark.parametrize("cell", sorted(FAULT_CELLS))
@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_faults_under_the_timed_path_are_not_correct(tiny_bench, fault,
                                                     cell):
    from bench.faults import FAULTS
    _, b = tiny_bench
    res = tiny_run(b, FAULT_CELLS[cell], 11, seconds=1.0 if cell == "chat"
                   else 0.5, fault=FAULTS[fault])
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


def run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         load_bench()["workloads"][0]["name"], "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_off_the_chip_no_result():
    p = run_script(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_without_the_program_no_result(tmp_path):
    b = load_bench()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in b["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d)
    p = run_script(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_reference_sals_with_a_full_budget_is_exact_attention():
    """With a full-rank projector and a budget that covers every
    selectable token, the SALS rows of the reference attend like exact
    attention, up to the int8 values; a small budget does not."""
    from bench.reference import dense_gqa
    cfg = tiny_config()
    cfg["sals"] = dict(cfg["sals"], rank_ratio=1.0)
    am = arch_module(cfg)
    arch = am.Arch(cfg)
    w = am.make_weights(arch, 5)
    u = am.make_projector(arch, w)["u"]
    rng = np.random.default_rng(0)
    toks = rng.integers(1, arch.vocab, 200).astype(np.int32)
    rows = np.arange(100, 228).clip(max=199).astype(np.int32)

    def hidden(n_critical, sals_layers):
        sp = dense_gqa.spec_for(arch, n_critical)
        return np.asarray(dense_gqa.final_hidden(
            w, u, sals_layers, sp, toks, 100, rows, 256))

    exact = hidden(16, [False] * arch.n_layers)
    full = hidden(256, arch.sals_layers)
    small = hidden(2, arch.sals_layers)
    scale = np.max(np.abs(exact))
    assert np.max(np.abs(full - exact)) < 0.02 * scale
    assert np.max(np.abs(small - exact)) > 5 * np.max(np.abs(full - exact))
