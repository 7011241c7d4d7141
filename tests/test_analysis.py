"""The analyzer analyzes itself: seeded-violation fixtures prove every
rule in the catalog FIRES, waiver fixtures prove every rule can be
waived with a justification, and the tier-1 gate runs the real tree
through the same entry point as ``python -m aios_tpu.analysis``.

Plus the runtime half: DebugLock unit tests that provoke and detect an
AB/BA lock-order inversion from two threads, and trip the held-too-long
watchdog.
"""

import textwrap
import threading
import time

import pytest

from aios_tpu.analysis import __main__ as analysis_cli
from aios_tpu.analysis.core import ModuleInfo
from aios_tpu.analysis.locks import (
    DebugLock,
    LockOrderError,
    make_lock,
    watchdog_trips,
)
from aios_tpu.analysis.registry import LOCKS, LockDecl, Registry
from aios_tpu.analysis.rules import RULE_IDS, Analyzer

FIX = "aios_tpu.fixture"


def _registry(**kw):
    locks = kw.pop("locks", (
        LockDecl("fix", FIX, "Eng", "_lock"),
        LockDecl("other", FIX, "Other", "_lock"),
    ))
    field_types = kw.pop("field_types", {
        (FIX, "Eng", "other"): (FIX, "Other"),
        (FIX, "Other", "eng"): (FIX, "Eng"),
    })
    return Registry(
        locks=locks,
        field_types=field_types,
        global_types={},
        context_fns=kw.pop("context_fns", {}),
        hook_targets={},
        local_locks={},
        local_types={},
        dispatch_hygiene_modules=kw.pop("dispatch_hygiene_modules", ()),
    )


def _analyze(src, registry=None, rules=None, doc=None):
    mi = ModuleInfo.from_source(
        textwrap.dedent(src), name=FIX, path="fixture.py"
    )
    return Analyzer(
        [mi], registry or _registry(), config_doc=doc
    ).run(rules)


def _unwaived(findings, rule=None):
    return [
        f for f in findings
        if not f.waived and (rule is None or f.rule == rule)
    ]


# -- rule 1: lock discipline -------------------------------------------------

DISPATCH_SRC = """
    class Eng:
        def f(self):
            with self._lock:
                fn = jax.jit(body)
"""

READBACK_SRC = """
    class Eng:
        def f(self):
            with self._lock:
                toks = np.asarray(device_tokens)
"""

RPC_SRC = """
    class Eng:
        def f(self):
            with self._lock:
                reply = self.runtime_stub.Infer(req)
"""


@pytest.mark.parametrize("src,rule", [
    (DISPATCH_SRC, "lock-dispatch"),
    (READBACK_SRC, "lock-readback"),
    (RPC_SRC, "lock-rpc"),
])
def test_lock_discipline_rules_fire(src, rule):
    found = _unwaived(_analyze(src), rule)
    assert len(found) == 1, f"{rule} did not fire"
    assert "fix" in found[0].message


@pytest.mark.parametrize("src,rule", [
    (DISPATCH_SRC, "lock-dispatch"),
    (READBACK_SRC, "lock-readback"),
    (RPC_SRC, "lock-rpc"),
])
def test_lock_discipline_waiver_honored(src, rule):
    waived = src.replace(
        "with self._lock:",
        f"with self._lock:  # aios: waive({rule}): fixture rationale",
    )
    findings = _analyze(waived)
    assert not _unwaived(findings, rule)
    assert any(
        f.rule == rule and f.waived
        and f.waive_reason == "fixture rationale"
        for f in findings
    )


def test_lock_discipline_engine_lock_allows_dispatch():
    """A lock declared with forbids=('readback', 'rpc') shelters
    dispatch by design (the engine lock's whole job)."""
    reg = _registry(locks=(
        LockDecl("fix", FIX, "Eng", "_lock", forbids=("readback", "rpc")),
    ))
    assert not _unwaived(_analyze(DISPATCH_SRC, reg), "lock-dispatch")
    assert _unwaived(_analyze(READBACK_SRC, reg), "lock-readback")


def test_lock_discipline_one_level_call_graph():
    src = """
        class Eng:
            def f(self):
                with self._lock:
                    self._helper()

            def _helper(self):
                toks = np.asarray(device_tokens)
    """
    found = _unwaived(_analyze(src), "lock-readback")
    assert len(found) == 1
    assert "_helper" in found[0].message


def test_lock_discipline_context_fn():
    """A function declared as running with a lock held (dynamic hook the
    AST can't follow) is scanned as if inside the lock body."""
    src = """
        class Eng:
            def hook(self):
                jax.block_until_ready(arrs)
    """
    reg = _registry(context_fns={(FIX, "Eng.hook"): ("fix",)})
    assert _unwaived(_analyze(src, reg), "lock-readback")


PLACEMENT_SRC = """
    class Eng:
        def admit(self, slot, ids):
            with self._lock:
                self.state = self._chunk_fn(8)(
                    self.state, {operand},
                )
"""
ENGINE_LIKE = (LockDecl("fix", FIX, "Eng", "_lock",
                        forbids=("readback", "rpc", "placement")),)


@pytest.mark.parametrize("operand,what", [
    ("jnp.int32(slot)", "jnp.int32"),
    ("jnp.float32(0.5)", "jnp.float32"),
    ("jnp.asarray(ids)", "jnp.asarray"),
    ("self.lengths.at[slot].set(0)", ".at[...].set"),
])
def test_a_placement_under_a_lock_that_names_it_is_of_the_readback_family(
        operand, what):
    """The shape ISSUE 41 removed from the scheduler's path: an operand put
    on the device one value at a time, or an eager ``.at[...]`` update,
    inside the engine lock. A lock that forbids "placement" reports it
    under ``lock-readback``; the other locks' discipline is what it was
    (``jnp.asarray`` is H2D, not a readback); numpy operands pass."""
    src = PLACEMENT_SRC.format(operand=operand)
    found = _unwaived(_analyze(src, _registry(locks=ENGINE_LIKE)),
                      "lock-readback")
    assert len(found) == 1 and what in found[0].message
    assert "numpy values" in found[0].message
    assert not _unwaived(_analyze(src), "lock-readback")
    numpy_operands = PLACEMENT_SRC.format(operand="np.int32(slot), padded")
    assert not _unwaived(_analyze(numpy_operands, _registry(locks=ENGINE_LIKE)))
    waived = src.replace(
        "with self._lock:",
        "with self._lock:  # aios: waive(lock-readback): behind the hand-over",
    )
    findings = _analyze(waived, _registry(locks=ENGINE_LIKE))
    assert not _unwaived(findings, "lock-readback")
    assert any(f.waived and f.waive_reason == "behind the hand-over"
               for f in findings)


def test_a_typed_local_resolves_to_its_class_s_lock():
    """``eng._lock`` in a driver object's method (ChunkedPrefill.step_async)
    is the engine's lock once the registry says what ``eng`` is: its body is
    held to the lock's discipline, and was not seen at all before."""
    src = """
        class Eng:
            pass

        class Driver:
            def step(self):
                eng = self.engine
                with eng._lock:
                    first = eng.fn(jnp.int32(self.slot))
    """
    reg = _registry(locks=ENGINE_LIKE)
    assert not _analyze(src, reg)
    reg.local_types[(FIX, "Driver.step", "eng")] = (FIX, "Eng")
    found = _unwaived(_analyze(src, reg), "lock-readback")
    assert len(found) == 1 and "jnp.int32" in found[0].message


def test_waiver_without_reason_rejected():
    waived = DISPATCH_SRC.replace(
        "with self._lock:",
        "with self._lock:  # aios: waive(lock-dispatch)",
    )
    findings = _analyze(waived)
    # the hazard still fires AND the empty waiver is its own finding
    assert _unwaived(findings, "lock-dispatch")
    assert _unwaived(findings, "waiver-reason")


def test_waiver_unknown_rule_rejected():
    findings = _analyze("""
        class Eng:
            def f(self):
                x = 1  # aios: waive(made-up-rule): because
    """)
    assert _unwaived(findings, "waiver-reason")


def test_standalone_waiver_line_governs_next_code_line():
    src = """
        class Eng:
            def f(self):
                with self._lock:
                    # aios: waive(lock-readback): fixture rationale
                    toks = np.asarray(device_tokens)
    """
    assert not _unwaived(_analyze(src), "lock-readback")


# -- rule 2: lock-order cycles ----------------------------------------------

def test_lock_order_cycle_detected():
    # Eng holds fix -> takes other; Other holds other -> calls back into
    # Eng.grab which takes fix: a classic AB/BA
    src = """
        class Eng:
            def a(self):
                with self._lock:
                    self.other.take()

            def grab(self):
                with self._lock:
                    pass

        class Other:
            def take(self):
                with self._lock:
                    pass

            def b(self):
                with self._lock:
                    self.eng.grab()
    """
    found = _unwaived(_analyze(src), "lock-order")
    assert len(found) == 1
    assert "fix" in found[0].message and "other" in found[0].message


def test_lock_order_acyclic_is_clean():
    src = """
        class Eng:
            def a(self):
                with self._lock:
                    self.other.take()

        class Other:
            def take(self):
                with self._lock:
                    pass
    """
    assert not _unwaived(_analyze(src), "lock-order")


# -- rule 3: guarded-by ------------------------------------------------------

GUARDED_SRC = """
    class Eng:
        def __init__(self):
            self._live = {}  #: guarded_by _lock

        def good(self):
            with self._lock:
                self._live[1] = "x"

        def bad(self):
            self._live.clear()
"""


def test_guarded_by_fires_on_unlocked_mutation():
    found = _unwaived(_analyze(GUARDED_SRC), "guarded-by")
    assert len(found) == 1
    assert "_live" in found[0].message
    # only the unlocked mutation fires — __init__ and the locked write
    # are allowed
    assert found[0].line == textwrap.dedent(GUARDED_SRC).splitlines().index(
        '        self._live.clear()'
    ) + 1


def test_guarded_by_waiver_honored():
    waived = GUARDED_SRC.replace(
        "self._live.clear()",
        "self._live.clear()  # aios: waive(guarded-by): fixture rationale",
    )
    assert not _unwaived(_analyze(waived), "guarded-by")


# -- rule 4: dispatch hygiene (jit-warmup) -----------------------------------

def test_jit_warmup_fires_off_warmup_path():
    src = """
        class Eng:
            def serve(self):
                fn = jax.jit(body)
                return fn(x)
    """
    reg = _registry(dispatch_hygiene_modules=(FIX,))
    found = _unwaived(_analyze(src, reg), "jit-warmup")
    assert len(found) == 1
    assert "serve" in found[0].message


def test_jit_warmup_reachable_from_registration_is_clean():
    src = """
        class Eng:
            def warmup(self):
                self.compile_step_fn(1)

            def compile_step_fn(self, n):
                self._store[n] = self._make_jit(n)

            def _make_jit(self, n):
                return jax.jit(body)
    """
    reg = _registry(dispatch_hygiene_modules=(FIX,))
    assert not _unwaived(_analyze(src, reg), "jit-warmup")


def test_jit_warmup_waiver_honored():
    src = """
        class Eng:
            def serve(self):
                fn = jax.jit(body)  # aios: waive(jit-warmup): fixture rationale
    """
    reg = _registry(dispatch_hygiene_modules=(FIX,))
    assert not _unwaived(_analyze(src, reg), "jit-warmup")


def test_jit_warmup_covers_draft_module():
    """ISSUE 11: the draft-model speculation module is serving-path —
    the rule must watch it (today its graphs are jitted from engine.py
    behind compile_draft_spec_fn/compile_draft_ingest_fns, which the
    WARMUP_ROOT_RE compile_* root already matches; a stray jax.jit added
    to spec.py itself must fail tier-1, not reach prod)."""
    from aios_tpu.analysis import registry as live_reg

    assert "aios_tpu.engine.spec" in live_reg.DISPATCH_HYGIENE_MODULES
    assert live_reg.WARMUP_ROOT_RE.match("compile_draft_spec_fn")
    assert live_reg.WARMUP_ROOT_RE.match("compile_draft_ingest_fns")


# -- rule: silent-except (ISSUE 10) ------------------------------------------

def _se_registry():
    r = _registry()
    r.silent_except_prefixes = (FIX,)
    return r


SILENT_SRC = """
    class Pool:
        def cleanup(self):
            try:
                self.batcher.shutdown()
            except Exception:
                pass
"""


def test_silent_except_fires_on_swallowed_broad_handler():
    found = _unwaived(_analyze(SILENT_SRC, _se_registry()), "silent-except")
    assert len(found) == 1
    assert "black hole" in found[0].message


def test_silent_except_waiver_honored():
    waived = SILENT_SRC.replace(
        "except Exception:",
        "except Exception:  # aios: waive(silent-except): fixture rationale",
    )
    assert not _unwaived(_analyze(waived, _se_registry()), "silent-except")


@pytest.mark.parametrize("body", [
    "raise",
    "log.exception('boom')",
    "log.warning('boom %s', exc)",
    "self._abort_all(exc)",
    "live.abort_reason = 'evicted: boom'",
    "self._finish(live, abort_reason='boom')",
    "context.abort(code, 'boom')",
])
def test_silent_except_recording_handlers_are_clean(body):
    src = f"""
        class Pool:
            def cleanup(self):
                try:
                    self.batcher.shutdown()
                except Exception as exc:
                    {body}
    """
    assert not _unwaived(_analyze(src, _se_registry()), "silent-except")


def test_silent_except_bare_and_tuple_handlers_count_as_broad():
    src = """
        class Pool:
            def a(self):
                try:
                    work()
                except:
                    pass

            def b(self):
                try:
                    work()
                except (ValueError, BaseException):
                    pass

            def c(self):
                try:
                    work()
                except ValueError:
                    pass  # narrow: not this rule's business
    """
    found = _unwaived(_analyze(src, _se_registry()), "silent-except")
    assert len(found) == 2


def test_silent_except_scoped_to_registry_prefixes():
    """A module outside the declared prefixes is not checked — the rule
    polices the serving plane, not every utility script."""
    assert not _unwaived(_analyze(SILENT_SRC, _registry()), "silent-except")


# -- rule 5: knob drift + metric catalog -------------------------------------

def test_knob_docs_missing_knob_fires_and_waives():
    src = """
        import os
        FLAG = os.environ.get("AIOS_TPU_FIXTURE_KNOB", "")
    """
    found = _unwaived(_analyze(src, doc="nothing here"), "knob-docs")
    assert len(found) == 1 and "AIOS_TPU_FIXTURE_KNOB" in found[0].message
    waived = src.replace(
        'FLAG = os.environ.get("AIOS_TPU_FIXTURE_KNOB", "")',
        'FLAG = os.environ.get("AIOS_TPU_FIXTURE_KNOB", "")'
        '  # aios: waive(knob-docs): fixture rationale',
    )
    assert not _unwaived(_analyze(waived, doc="nothing"), "knob-docs")


def test_knob_docs_stale_doc_row_fires():
    found = _unwaived(
        _analyze("x = 1", doc="| `AIOS_TPU_GONE_KNOB` | old |"),
        "knob-docs",
    )
    assert len(found) == 1
    assert found[0].path.endswith("CONFIG.md")
    assert "AIOS_TPU_GONE_KNOB" in found[0].message


def test_metric_catalog_fires_outside_instruments():
    src = """
        COUNT = Counter("aios_tpu_fixture_total", "help", ("model",))
    """
    found = _unwaived(_analyze(src), "metric-catalog")
    assert len(found) == 1
    waived = src.replace(
        '("model",))',
        '("model",))  # aios: waive(metric-catalog): fixture rationale',
    )
    assert not _unwaived(_analyze(waived), "metric-catalog")


def test_metric_catalog_ignores_collections_counter():
    src = """
        import collections
        by_cat = collections.Counter(e["category"] for e in events)
    """
    assert not _unwaived(_analyze(src), "metric-catalog")


# -- the real tree, through the CLI entry point ------------------------------

def test_tree_is_clean():
    """Zero unwaived findings on the shipped tree — THE tier-1 gate,
    through the exact entry point ``python -m aios_tpu.analysis`` uses,
    so local runs and CI cannot diverge."""
    assert analysis_cli.main([]) == 0


def test_cli_rule_filter_and_json(capsys):
    import json

    assert analysis_cli.main(["--rule", "lock-order", "--json"]) == 0
    out = capsys.readouterr().out
    assert isinstance(json.loads(out), list)


def test_cli_list_rules(capsys):
    assert analysis_cli.main(["--list-rules"]) == 0
    listed = capsys.readouterr().out.split()
    assert listed == list(RULE_IDS)


def test_registry_locks_all_wired_to_make_lock():
    """Every declared lock is constructed through make_lock(<name>) in
    its declared module (the static registry and the runtime DebugLock
    names must agree, or AIOS_TPU_LOCK_DEBUG verifies a different lock
    set than the analyzer defends)."""
    import importlib

    from aios_tpu.analysis.core import module_info_for, string_call_args

    wired = set()
    for decl in LOCKS:
        mod = importlib.import_module(decl.module)
        mi = module_info_for(mod)
        names = {
            lit for lit, _ in string_call_args(mi.tree, ("make_lock",))
        }
        assert decl.name in names, (
            f"{decl.module} never calls make_lock({decl.name!r})"
        )
        wired.add(decl.name)
    assert wired == {d.name for d in LOCKS}


# -- DebugLock runtime half --------------------------------------------------

def test_debug_lock_detects_ab_ba_inversion():
    """Two threads acquiring two lock roles in opposite orders: the
    second ordering raises LockOrderError carrying both stacks."""
    a = DebugLock("t_inv_a")
    b = DebugLock("t_inv_b")

    def order_ab():
        with a:
            with b:
                pass

    t = threading.Thread(target=order_ab)
    t.start()
    t.join()

    caught = []

    def order_ba():
        try:
            with b:
                with a:  # closes the cycle -> raises
                    pass
        except LockOrderError as e:
            caught.append(e)

    t2 = threading.Thread(target=order_ba)
    t2.start()
    t2.join()
    assert len(caught) == 1
    msg = str(caught[0])
    assert "t_inv_a" in msg and "t_inv_b" in msg
    assert "current acquisition" in msg
    assert "opposite order" in msg
    # the failed acquire left nothing held: b released by the context
    # manager, a never acquired
    assert not a.locked() and not b.locked()


def test_debug_lock_roles_not_instances():
    """Two instances of the SAME role nested do not form an edge (two
    replicas' batcher locks are one role), but opposite-order roles
    across DIFFERENT instances still trip."""
    a1, a2 = DebugLock("t_role_a"), DebugLock("t_role_a")
    with a1:
        with a2:  # same role: no self-edge, no raise
            pass
    b = DebugLock("t_role_b")
    with a1:
        with b:
            pass
    with pytest.raises(LockOrderError):
        with b:
            with a2:  # a-role then b-role was recorded via a1
                pass


def test_debug_lock_watchdog_trips(monkeypatch):
    monkeypatch.setenv("AIOS_TPU_LOCK_WATCHDOG_SECS", "0.05")
    lk = DebugLock("t_watchdog")
    before = len(watchdog_trips())
    with lk:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            trips = watchdog_trips()[before:]
            if any(t["lock"] == "t_watchdog" for t in trips):
                break
            time.sleep(0.02)
    trips = [t for t in watchdog_trips()[before:]
             if t["lock"] == "t_watchdog"]
    assert trips, "watchdog never tripped on a 0.05s threshold"
    assert trips[0]["held_secs"] >= 0.05
    assert trips[0]["stack"]  # the holder's live stack was captured


def test_make_lock_honors_debug_flag(monkeypatch):
    monkeypatch.setenv("AIOS_TPU_LOCK_DEBUG", "1")
    assert isinstance(make_lock("t_flag"), DebugLock)
    monkeypatch.setenv("AIOS_TPU_LOCK_DEBUG", "0")
    lk = make_lock("t_flag")
    assert isinstance(lk, type(threading.Lock()))


def test_debug_lock_is_a_lock():
    """Context manager + acquire/release/locked surface parity."""
    lk = DebugLock("t_surface")
    assert lk.acquire(blocking=False)
    assert lk.locked()
    assert not lk.acquire(blocking=False)
    lk.release()
    assert not lk.locked()
    with lk:
        assert lk.locked()
    assert not lk.locked()
