"""The program-build ledger (ISSUE 50, ``obs/ledger.py``): where a
program is traced, lowered, compiled and first run is a span
``build/<component>/<family>``, the seconds inside it are JAX's own
(``jax.monitoring``), and nothing stays on the tick's or the step's
path after a program's first call."""

import threading

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring as jax_monitoring

from edl_tpu.cluster.state import State
from edl_tpu.models import TransformerConfig, TransformerLM
from edl_tpu.obs import metrics as obs_metrics
from edl_tpu.obs import trace as obs_trace
from edl_tpu.obs.ledger import (BUILD_STAGES, PROGRAM_BUILDS,
                                ProgramBuildLedger, _FirstCall)
from edl_tpu.serving import ContinuousBatcher
from edl_tpu.train import ElasticTrainer, TrainConfig
from edl_tpu.utils import compile_cache

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"


@pytest.fixture(scope="module", autouse=True)
def listening():
    """The ledger's listeners on, as ``enable_compile_cache()`` turns
    them on in every compiling entry point (not through it: it would
    point the suite's compiles at ``.jax_cache``)."""
    compile_cache._listen_to_builds()


def _rows(since: dict | None = None) -> dict:
    """``{"<kind>/<component>/<family>": {field: growth}}`` of the
    process's ledger, rows that did not grow left out."""
    out: dict = {}
    for k, v in PROGRAM_BUILDS.totals().items():
        row, field = k.rsplit("/", 1)
        grown = v - (since or {}).get(k, 0)
        if grown:
            out.setdefault(row, {})[field] = grown
    return out


class _Spans:
    """The ledger's trace events, through a tap."""

    def __init__(self):
        self.events: list[dict] = []

    def __call__(self, rec):
        if rec["name"].startswith(("build/", "setup/")):
            self.events.append(rec)

    def __enter__(self):
        obs_trace.add_tap(self)
        return self

    def __exit__(self, *exc):
        obs_trace.remove_tap(self)


# -- the listeners -----------------------------------------------------------

def test_enable_compile_cache_twice_registers_one_set_of_listeners(
        monkeypatch, tmp_path):
    # with the variable set it touches no jax setting; nothing compiles
    # in here, so jax never opens a cache at this path
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    durations = jax_monitoring.get_event_duration_listeners()
    events = jax_monitoring.get_event_listeners()
    assert durations.count(PROGRAM_BUILDS.on_duration) == 1
    assert events.count(PROGRAM_BUILDS.on_event) == 1


# -- the arithmetic, on a ledger of its own ----------------------------------

def test_nested_events_add_up_to_the_outermost():
    """A trace reports its whole length at its end, its callees' traces
    before it: each is booked its own time only."""
    led = ProgramBuildLedger()
    with led.build("engine", "prefill", key=(8, 1)):
        led.on_duration(TRACE, 0.010, fun_name="inner")
        led.on_duration(TRACE, 0.020, fun_name="inner2")
        # 60 ms that hold both callees
        led.on_duration(TRACE, 1000.0, fun_name="outer")
        led.on_duration(LOWER, 0.0, fun_name="jit(outer)")
        led.on_duration(COMPILE, 0.0, fun_name="jit(outer)")
    t = led.totals()
    assert t["build/engine/prefill/trace_s"] == pytest.approx(1000.0)
    assert t["build/engine/prefill/builds"] == 1


def test_cache_hits_misses_and_none():
    led = ProgramBuildLedger()
    with led.build("kv", "pool_commit", key=3):
        led.on_event(REQUEST)
        led.on_event(HIT)
        led.on_duration(COMPILE, 0.0, fun_name="jit(scatter)")
        led.on_event(REQUEST)                   # asked, not found
        led.on_duration(COMPILE, 0.0, fun_name="jit(scatter)")
        led.on_duration(COMPILE, 0.0, fun_name="jit(x)")  # never asked
    t = led.totals()
    assert t["build/kv/pool_commit/builds"] == 3
    assert t["build/kv/pool_commit/cache_hits"] == 1
    assert t["build/kv/pool_commit/cache_misses"] == 1


def test_a_build_span_that_built_nothing_books_nothing_a_setup_span_does():
    led = ProgramBuildLedger()
    with led.build("engine", "step"):
        pass
    assert led.totals() == {}
    with led.setup("engine"):
        pass
    t = led.totals()
    assert t["setup/engine/state/run_s"] >= 0.0
    assert t["setup/engine/state/builds"] == 0


def test_a_span_inside_another_is_deducted_from_it():
    led = ProgramBuildLedger()
    with led.setup("engine"):
        with led.build("engine", "zeros"):
            led.on_duration(COMPILE, 0.0, fun_name="jit(zeros)")
            threading.Event().wait(0.05)
    t = led.totals()
    assert t["build/engine/zeros/run_s"] >= 0.05
    assert t["setup/engine/state/run_s"] < 0.04


def test_an_event_outside_any_span_goes_to_other_under_its_own_name():
    led = ProgramBuildLedger()
    led.on_duration(LOWER, 0.25, fun_name="jit(convert_element_type)")
    # (a length of 0: one that reached back past the event before it
    # would be taken to contain it)
    led.on_duration(COMPILE, 0.0, fun_name="jit(convert_element_type)")
    assert led.totals()["build/other/convert_element_type/lower_s"] == 0.25
    assert led.totals()["build/other/convert_element_type/builds"] == 1
    assert led.thread_totals(threading.get_ident()) == (1, 0.25)


def test_totals_are_flat_and_numeric():
    jnp.arange(7).sum().block_until_ready()      # at least one row
    totals = PROGRAM_BUILDS.totals()
    assert totals
    for key, value in totals.items():
        kind, component, rest = key.split("/", 2)
        family, field = rest.rsplit("/", 1)
        assert kind in ("build", "setup") and component and family
        assert field in ("builds", "cache_hits", "cache_misses",
                         *(f"{s}_s" for s in BUILD_STAGES))
        assert isinstance(value, (int, float)) and not isinstance(value, bool)


def test_the_registry_has_the_two_families_and_no_third():
    with PROGRAM_BUILDS.build("engine", "test_family"):
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((5,))).block_until_ready()
    mine = sorted(n for n in obs_metrics.REGISTRY._metrics
                  if n.startswith("edl_program_"))
    assert mine == ["edl_program_build_seconds_total",
                    "edl_program_builds_total"]
    page = obs_metrics.REGISTRY.render()
    assert 'edl_program_builds_total{component="engine",cache="' in page
    assert ('edl_program_build_seconds_total{component="engine",'
            'stage="compile"}') in page


# -- threads -----------------------------------------------------------------

def test_a_compile_on_another_thread_is_booked_to_that_threads_label():
    """The loop has a span open; a background thread compiles under its
    own label, and a third with no span at all goes to ``other``."""
    x = jnp.ones((6,))          # its own one-op program, before the count
    before = PROGRAM_BUILDS.totals()
    idents = {}

    def labelled():
        idents["labelled"] = threading.get_ident()
        with PROGRAM_BUILDS.build("train", "test_background"):
            jax.jit(lambda x: x * 5 - 2)(x).block_until_ready()

    def unlabelled():
        idents["unlabelled"] = threading.get_ident()
        jax.jit(lambda x: x * 7 - 3)(x).block_until_ready()

    with PROGRAM_BUILDS.build("train", "test_loop"):
        for fn in (labelled, unlabelled):
            th = threading.Thread(target=fn)
            th.start()
            th.join()
    rows = _rows(before)
    assert rows["build/train/test_background"]["builds"] == 1
    assert "build/train/test_loop" not in rows      # it built nothing
    assert rows["build/other/<lambda>"]["builds"] == 1
    assert PROGRAM_BUILDS.thread_totals(idents["labelled"])[0] == 1
    assert PROGRAM_BUILDS.thread_totals(idents["unlabelled"])[0] == 1


# -- the engine --------------------------------------------------------------

@pytest.fixture(scope="module")
def warmed():
    """A small paged engine, warmed for both its buckets under a tap."""
    cfg = TransformerConfig(vocab_size=97, num_layers=2, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=64,
                            remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    before = PROGRAM_BUILDS.totals()
    with _Spans() as spans:
        eng = ContinuousBatcher(
            cfg, params, slots=3, prefill_buckets=(8, 16), temperature=0.0,
            steps_per_sync=2, kv_block=4, kv_pool_blocks=64, prefill_chunk=8)
        eng.warm(8)
        eng.warm(16)
    yield eng, _rows(before), spans.events
    eng.stop()


def test_warm_books_every_family_it_builds_under_its_name(warmed):
    eng, rows, _events = warmed
    built = {r.split("/", 2)[2] for r in rows
             if r.startswith("build/engine/") and rows[r].get("builds")}
    assert built == {"prefill", "insert", "step", "chunk", "chunkfin",
                     "chunk_start", "reuse", "zeros"}
    # one prefill program a (bucket, admission-group size), one insert a
    # group size, one reuse program a (suffix bucket, padded depth)
    assert rows["build/engine/prefill"]["builds"] == 2 * len(eng.PREFILL_KS)
    assert rows["build/engine/insert"]["builds"] == len(eng.PREFILL_KS)
    assert rows["build/engine/step"]["builds"] == 1
    assert rows["setup/engine/state"]["run_s"] > 0
    for row in rows.values():
        assert all(row.get(f"{s}_s", 0) >= 0 for s in BUILD_STAGES)


def test_the_jax_stages_fit_inside_their_span(warmed):
    _eng, _rows_, events = warmed
    spans = [e for e in events if e["name"].startswith("build/engine/")]
    assert len(spans) >= 10
    for e in spans:
        staged = e["trace_s"] + e["lower_s"] + e["compile_s"]
        assert staged <= e["dur"] + 1e-3, e
        assert e["run_s"] == pytest.approx(e["dur"] - staged, abs=2e-3)
        assert e["cache"] in ("hit", "miss", "none") and e["programs"] >= 1
    # the span carries the program's key
    assert any(e["name"] == "build/engine/prefill" and e["key"] == "(8, 1)"
               for e in spans)


def test_a_second_warm_and_the_first_tick_add_no_build(warmed):
    """``warm()`` compiles step and insert through ``lower().compile()``
    and the tick then calls the jitted functions: in this jax the two
    share the lowering and the executable, so neither is built twice.
    Only the pool-commit program, which ``warm()`` leaves to its first
    commit, is built by the first request."""
    eng, _rows_, _events = warmed
    eng.run_on_engine(lambda: None)     # an earlier test's commit is over
    before = PROGRAM_BUILDS.totals()
    eng.warm(8)
    eng.warm(16)
    assert not [r for r, row in _rows(before).items() if row.get("builds")]
    n0, s0 = (eng.stats()[k] for k in ("program_builds", "program_build_s"))
    # a commit size no other test of this file reaches: 3 blocks of 4
    out = eng.submit(np.arange(1, 10, dtype=np.int32), 4).result(timeout=120)
    assert len(out) == 4
    # the answer resolves before its tick commits to the pool: a task
    # runs between ticks, so this returns after that commit
    eng.run_on_engine(lambda: None)
    rows = _rows(before)
    assert not [r for r, row in rows.items()
                if r.startswith("build/engine/") and row.get("builds")]
    assert rows["build/kv/pool_commit"]["builds"] == 1
    # what the ENGINE THREAD built is in stats(): that commit program
    # and the one-op programs of its first admission
    n1, s1 = (eng.stats()[k] for k in ("program_builds", "program_build_s"))
    assert n1 - n0 >= 1 and s1 > s0


def test_after_its_first_call_the_memo_holds_the_bare_jitted_function(warmed):
    eng, _rows_, _events = warmed
    jitted = type(jax.jit(lambda: 0))
    eng.submit(np.arange(1, 6, dtype=np.int32), 2).result(timeout=120)
    eng.run_on_engine(lambda: None)     # past the tick's pool commit
    for key, fn in eng._prefill_cache.items():
        assert type(fn) is jitted, key
    for key, fn in eng._kv._jit_cache.items():
        assert type(fn) is jitted, key
    assert type(eng._step_jit) is jitted and type(eng._insert_jit) is jitted
    # a family not yet called is still in its wrapper, and the wrapper
    # hands back the very function it wraps
    cold = eng._chunk_final_fn(16)
    assert isinstance(cold, _FirstCall)
    assert eng._prefill_cache[("chunkfin", 16)] is cold
    assert type(cold.fn) is jitted and cold.lower == cold.fn.lower


def test_a_first_call_leaves_someone_elses_wrapper_in_place():
    """The benchmark and the tests put their own callable where the
    program was: the first call must not take it away."""
    class Holder:
        pass

    h = Holder()
    h.fn = PROGRAM_BUILDS.first_call(jax.jit(lambda x: x + 11), "engine",
                                     "test_held", None, h, "fn")
    wrapped = h.fn

    def tap(x):
        return wrapped(x)

    h.fn = tap
    assert int(h.fn(jnp.asarray(1))) == 12
    assert h.fn is tap
    assert int(wrapped(jnp.asarray(2))) == 13       # a plain forward now


def test_stats_gains_two_keys_and_no_third(warmed):
    eng, _rows_, _events = warmed
    mine = sorted(k for k in eng.stats() if "build" in k and "program" in k)
    assert mine == ["program_build_s", "program_builds"]
    assert not [k for k in eng.stats() if k.startswith(("build/", "setup/"))]


# -- the trainer -------------------------------------------------------------

def _linear_loss(params, extra, batch, rng):
    loss = jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)
    return loss, (extra, {})


def _batches(n):
    rng = np.random.default_rng(0)
    for _ in range(n):
        x = rng.normal(size=(16, 5)).astype(np.float32)
        yield {"x": x, "y": x.sum(-1, keepdims=True)}


def test_the_trainer_books_its_state_its_step_and_its_background_compile():
    before = PROGRAM_BUILDS.totals()
    tr = ElasticTrainer(_linear_loss, TrainConfig(log_every=0))
    state = tr.create_state(lambda: ({"w": jnp.zeros((5, 1))}, None),
                            optax.sgd(0.1))
    wrapper = tr.step_fn
    assert isinstance(wrapper, _FirstCall)
    tr.fit(state, State(), lambda e: _batches(3), epochs=1)
    # the live-MFU compile runs on its own thread: wait for its span
    for th in threading.enumerate():
        if th.name == "edl-mfu-cost-analysis":
            th.join(60)
    rows = _rows(before)
    assert rows["setup/train/state"]["builds"] >= 1
    assert rows["build/train/step"]["builds"] == 1
    assert rows["build/train/step"]["run_s"] > 0
    # the step and the loop call the same jitted object as before
    assert tr.step_fn is wrapper.fn
    flops = rows.get("build/train/step_flops", {})
    # in this jax lower().compile() finds the step's executable: the
    # background thread's span then built nothing and booked nothing
    assert flops.get("builds", 0) in (0, 1)


def test_many_threads_lose_no_update():
    """More threads than cores, each with its own spans and its own
    unlabelled compiles, under a short switch interval: every program
    and every second is in the rows and in its thread's sums."""
    import sys
    led = ProgramBuildLedger()
    threads, per = 16, 200
    idents = []
    together = threading.Barrier(threads)   # idents of live threads differ

    def work(i):
        idents.append(threading.get_ident())
        together.wait(30)
        for n in range(per):
            with led.build("engine", f"family{i % 3}", key=n):
                led.on_event(REQUEST)
                led.on_duration(COMPILE, 0.0, fun_name="jit(f)")
            led.on_duration(COMPILE, 0.0, fun_name="jit(shared)")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ths = [threading.Thread(target=work, args=(i,))
               for i in range(threads)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(60)
        assert not any(th.is_alive() for th in ths)
    finally:
        sys.setswitchinterval(old)
    t = led.totals()
    assert sum(t[f"build/engine/family{k}/builds"] for k in range(3)) == \
        threads * per
    assert sum(t[f"build/engine/family{k}/cache_misses"]
               for k in range(3)) == threads * per
    assert t["build/other/shared/builds"] == threads * per
    assert [led.thread_totals(i)[0] for i in idents] == [2 * per] * threads
