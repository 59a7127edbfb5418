"""The span-and-count recorder (``repro.spans``) and where the front doors
use it: nesting, parent and root ids, counts rolling up, a span closed by
an exception, the record's bound, compilations and persistent-cache loads
counted in the span that caused them; the span tree of a tiny ``run_fl``
and ``run_rounds``; the spans on the profiler's host plane; and the
device name scopes in the lowered training and selection steps."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs.paper_resnet_speech import reduced
from repro.core import (
    EnergyModel,
    SelectorConfig,
    SelectorState,
    make_population,
)
from repro.federated import FLConfig, run_fl, run_rounds
from repro.federated.server import (
    _fused_runner,
    _fused_setup,
    _fused_statics,
    _history_from_traj,
)
from repro.federated.simulation import (
    BudgetLedger,
    _scanned_runner,
    round_cost_table,
)

SETUP_CHILDREN = ["fl.setup.data", "fl.setup.model", "fl.setup.fleet",
                  "fl.setup.cost_table", "fl.setup.runner",
                  "fl.setup.eval0"]
SCOPES = ("select", "energy_sim", "cohort_sgd", "aggregate", "eval")


def _cfg():
    return FLConfig(selector=SelectorConfig(kind="eafl", k=4), n_clients=24,
                    rounds=3, local_steps=2, batch_size=8,
                    samples_per_client=24, eval_every=2, eval_samples=70,
                    model=reduced(), input_hw=16)


def _call(root_name):
    """The spans of the newest closed ``root_name`` call, by id."""
    rec = list(spans.recent())
    root = [s for s in rec if s.name == root_name and s.parent is None][-1]
    return {s.id: s for s in rec if s.root == root.id}, root


# ------------------------------------------------------------- recorder
def test_spans_nest_with_parent_and_root_ids():
    with spans.span("a") as a:
        with spans.span("b") as b:
            with spans.span("c") as c:
                pass
        with spans.span("d") as d:
            pass
    assert a.parent is None and a.root == a.id
    assert (b.parent, c.parent, d.parent) == (a.id, b.id, a.id)
    assert {b.root, c.root, d.root} == {a.id}
    assert a.start_ns <= b.start_ns <= c.start_ns <= c.end_ns <= b.end_ns \
        <= d.start_ns <= d.end_ns <= a.end_ns
    # closed innermost first
    assert [s.name for s in list(spans.recent())[-4:]] == ["c", "b", "d",
                                                           "a"]


def test_counts_roll_up_to_the_parent():
    spans.count("outside", 5)  # no open span: not recorded anywhere
    with spans.span("a") as a:
        spans.count("n", 1)
        with spans.span("b") as b:
            spans.count("n", 2)
            spans.count("m", 3)
        with spans.span("c") as c:
            spans.count("n", 4)
    assert b.counts == {"n": 2, "m": 3} and c.counts == {"n": 4}
    assert a.counts == {"n": 7, "m": 3}


def test_a_device_count_is_kept_without_a_transfer_and_read_as_an_int():
    picks = jnp.arange(5, dtype=jnp.int32)       # on the device
    three = jnp.asarray(3, jnp.int32)
    with spans.span("outer") as outer:
        with jax.transfer_guard_device_to_host("disallow"):
            with spans.span("inner") as inner:
                spans.count("picks", picks)
                spans.count("picks", three)
                spans.count("picks", 2)
                spans.count("slots", np.array([4, 4]))
    rec = spans.recent()
    assert inner.counts == {"picks": 15, "slots": 8}
    assert outer.counts == {"picks": 15, "slots": 8}
    assert all(type(v) is int for s in rec for v in s.counts.values())


def test_a_span_left_by_an_exception_is_closed_and_recorded():
    with pytest.raises(RuntimeError):
        with spans.span("outer") as outer:
            with spans.span("failing") as failing:
                spans.count("k", 1)
                raise RuntimeError("boom")
    assert failing.end_ns >= failing.start_ns > 0
    assert outer.counts == {"k": 1}
    assert [s.name for s in list(spans.recent())[-2:]] == ["failing",
                                                           "outer"]
    # the stack unwound: a new span is a root again
    with spans.span("after") as after:
        pass
    assert after.parent is None


def test_recent_keeps_the_newest_spans_up_to_its_bound():
    n = spans.RECENT_MAX + 7
    for i in range(n):
        with spans.span(f"s{i}"):
            pass
    rec = spans.recent()
    assert len(rec) == spans.RECENT_MAX
    assert rec[-1].name == f"s{n - 1}" and rec[0].name == "s7"


def test_a_compilation_counts_in_the_span_that_caused_it():
    x = jnp.ones(3)
    with spans.span("call") as call:
        with spans.span("lowers") as lowers:
            # a fresh function object misses the in-memory cache
            jax.jit(lambda v: v * 7 - 2)(x).block_until_ready()
        with spans.span("host") as host:
            sum(range(10))
    assert lowers.counts["xla.programs"] >= 1
    assert "xla.cache_loads" not in lowers.counts  # no persistent cache
    assert host.counts == {}
    assert call.counts == lowers.counts


@pytest.fixture
def persistent_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
        cc.reset_cache()


def test_a_persistent_cache_load_counts_where_it_happened(persistent_cache):
    x = jnp.arange(5.0)
    with spans.span("first") as first:
        jax.jit(lambda v: jnp.cumsum(v) * 3)(x).block_until_ready()
    with spans.span("again") as again:
        jax.jit(lambda v: jnp.cumsum(v) * 3)(x).block_until_ready()
    assert first.counts.get("xla.cache_loads", 0) == 0
    assert again.counts == {"xla.programs": 1, "xla.cache_loads": 1}


# ---------------------------------------------------------- front doors
@pytest.fixture(scope="module")
def traced_run_fl(tmp_path_factory):
    """One tiny ``run_fl`` on the fused scan, under the profiler."""
    d = str(tmp_path_factory.mktemp("profile"))
    cfg = _cfg()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        hist = run_fl(cfg, engine="scanned")
    finally:
        jax.profiler.stop_trace()
    by_id, root = _call("run_fl")
    return cfg, hist, by_id, root, d


def test_run_fl_span_tree_and_order(traced_run_fl):
    cfg, hist, by_id, root, _ = traced_run_fl
    children = lambda s: sorted((c for c in by_id.values()
                                 if c.parent == s.id),
                                key=lambda c: c.start_ns)
    top = children(root)
    assert [c.name for c in top] == ["fl.setup", "fl.scan", "fl.history"]
    assert [c.name for c in children(top[0])] == SETUP_CHILDREN
    assert all(not children(c) for c in top[1:])
    # siblings do not overlap and stay inside their parent
    for parent in [root, top[0]]:
        kids = children(parent)
        assert parent.start_ns <= kids[0].start_ns
        assert kids[-1].end_ns <= parent.end_ns
        for a, b in zip(kids, kids[1:]):
            assert a.end_ns <= b.start_ns


def test_run_fl_counts_its_sgd_slots(traced_run_fl):
    cfg, hist, by_id, root, _ = traced_run_fl
    history = next(s for s in by_id.values() if s.name == "fl.history")
    trained = cfg.rounds * cfg.selector.k
    assert history.counts["sgd.slots_trained"] == trained
    # no overcommit, faults or quarantine: every success is aggregated
    succeeded = round(sum(p * cfg.selector.k for p in hist.participation))
    assert history.counts["sgd.slots_aggregated"] == succeeded
    assert 0 < succeeded <= trained
    assert root.counts["sgd.slots_trained"] == trained
    # the first call compiles every program it runs
    assert root.counts["xla.programs"] >= 2


def test_history_counts_only_slots_in_applied_updates():
    """Quarantined slots and every slot of a round whose update was
    skipped trained for nothing."""
    cfg = _cfg()
    succeeded = np.array([[1, 1, 1, 0], [1, 1, 1, 1], [1, 1, 0, 0]], bool)
    r = cfg.rounds
    traj = {"round_duration": np.ones(r, np.float32),
            "new_dropouts": np.zeros(r, np.int32),
            "succeeded": succeeded, "chosen": np.ones((r, 4), bool),
            "slot_losses": np.ones((r, 4), np.float32),
            "test_acc": np.zeros(r, np.float32),
            "fairness": np.ones(r, np.float32),
            "mean_battery": np.ones(r, np.float32),
            "quarantined": np.array([0, 1, 2], np.int32),
            "update_skipped": np.array([0, 0, 1], np.int32)}
    with spans.span("fl.history") as s:
        _history_from_traj(cfg, 0.0, traj)
    assert s.counts["sgd.slots_trained"] == 12
    assert s.counts["sgd.slots_aggregated"] == 3 + (4 - 1)


def test_run_fl_spans_on_the_profilers_host_plane(traced_run_fl):
    *_, root, d = traced_run_fl
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = ProfileData.from_file(path)
    names = {e.name for p in pd.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert {"run_fl", "fl.setup", "fl.scan", "fl.history",
            *SETUP_CHILDREN} <= names


def test_run_rounds_is_one_span_without_children():
    sel = SelectorConfig(kind="eafl", k=4)
    pop = make_population(jax.random.PRNGKey(3), 64)
    run_rounds(jax.random.PRNGKey(4), sel, pop, SelectorState.create(sel),
               EnergyModel(), 85e6, 40, 20, 3)
    by_id, root = _call("run_rounds")
    assert list(by_id) == [root.id]
    assert set(root.counts) <= {"xla.programs", "xla.cache_loads",
                                "topk.picks", "topk.pick_slots"}
    # lax.top_k on the CPU: no Pallas picks, no slots
    assert root.counts["topk.picks"] == root.counts["topk.pick_slots"] == 0


def test_run_rounds_counts_the_pallas_picks():
    sel = SelectorConfig(kind="eafl", k=4)
    pop = make_population(jax.random.PRNGKey(3), 20_000)
    _, _, traj = run_rounds(jax.random.PRNGKey(4), sel, pop,
                            SelectorState.create(sel), EnergyModel(), 85e6,
                            40, 20, 3, use_pallas=True, interpret=True)
    _, root = _call("run_rounds")
    # two top-ks a round, each over 5 blocks of 4096
    assert root.counts["topk.pick_slots"] == 3 * 2 * 5 * 4
    assert root.counts["topk.picks"] == int(np.sum(traj["topk_picks"]))
    assert 0 < root.counts["topk.picks"] < 3 * 2 * 5 * 4


# ------------------------------------------------------- device scopes
def _scopes(lowered):
    """The scopes the lowered program's op locations name: each op's
    location holds its name stack, ``.../<scope>/<op>``."""
    text = lowered.as_text(debug_info=True)
    return {s for s in SCOPES if re.search(rf'["/]{s}/', text)}


def test_training_step_names_its_device_layers():
    cfg = _cfg()
    (kloop, data, test, params, opt_state, pop, sim_steps, up_bytes,
     energy_model, model_bytes) = _fused_setup(cfg)
    t_total, cost = round_cost_table(pop, energy_model, model_bytes,
                                     sim_steps, cfg.batch_size, up_bytes)
    run, evaluate = _fused_runner(cfg.model, *_fused_statics(cfg), False,
                                  True)
    st = SelectorState.create(cfg.selector).canonical()
    carry = (params, opt_state, pop, st, kloop, jnp.float32(0),
             BudgetLedger.create())
    lowered = run.lower(jnp.asarray(np.array([False, True, True])), carry,
                        data["x"], data["y"], test["x"], test["y"],
                        t_total, cost)
    assert _scopes(lowered) == set(SCOPES)
    assert _scopes(evaluate.lower(params, test["x"], test["y"])) == {"eval"}


def test_selection_step_names_its_device_layers():
    sel = SelectorConfig(kind="eafl", k=4)
    pop = make_population(jax.random.PRNGKey(3), 64)
    run = _scanned_runner(sel, EnergyModel(), 85e6, 40, 20, None, None,
                          False, True, None)
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    lowered = run.lower(keys, pop, SelectorState.create(sel).canonical())
    assert _scopes(lowered) == {"select", "energy_sim"}
    # the scope names the whole layer, not a stray op
    text = lowered.as_text(debug_info=True)
    assert len(re.findall(r'["/]select/', text)) > 10
