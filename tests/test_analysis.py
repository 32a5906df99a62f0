"""Contract-analyzer tests (torchmpi_tpu/analysis/): each pass MUST catch
its seeded-bad fixture, and the real tree MUST run clean — the analyzers
are only worth their tier-1 seconds if silence means something.

The seeded fixtures are text/callable inputs to the pure pass cores (no
temp repos, no subprocesses); the clean-tree checks run the repo-shaped
assemblers.  The full CLI over the whole program registry and the
sanitizer drill are the ``slow``-marked tests at the bottom.
"""

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from torchmpi_tpu.analysis import (abi, jaxpr_lint, knobs, locks, registry,
                                   threads, wire)

REPO = Path(__file__).resolve().parents[1]

pytestmark = pytest.mark.analysis


# ------------------------------------------------------------------- ABI

GOOD_CPP = """
#include <cstdint>
extern "C" {
int tmpi_x_create(int rank, const char* spec, uint64_t n) { return 1; }
void tmpi_x_free(int id) {}
uint64_t tmpi_x_count() { return 0; }
int tmpi_x_push(int id, const void* data, uint64_t count) { return 1; }
}
"""

GOOD_PY = """
import ctypes
i32, u64, vp = ctypes.c_int, ctypes.c_uint64, ctypes.c_void_p
L = ctypes.CDLL("x.so")
L.tmpi_x_create.argtypes = [i32, ctypes.c_char_p, u64]
L.tmpi_x_create.restype = i32
L.tmpi_x_free.argtypes = [i32]
L.tmpi_x_free.restype = None
L.tmpi_x_count.argtypes = []
L.tmpi_x_count.restype = u64
L.tmpi_x_push.argtypes = [i32, vp, u64]
L.tmpi_x_push.restype = i32
"""


class TestAbiChecker:
    def _codes(self, cpp, py):
        return [f.code for f in abi.check_abi_pair(cpp, py, "x.cpp", "x.py",
                                                   symbol_prefix="tmpi_x_")]

    def test_clean_pair_is_silent(self):
        assert self._codes(GOOD_CPP, GOOD_PY) == []

    def test_wrong_arity_flagged(self):
        bad = GOOD_PY.replace(
            "L.tmpi_x_create.argtypes = [i32, ctypes.c_char_p, u64]",
            "L.tmpi_x_create.argtypes = [i32, ctypes.c_char_p]")
        assert "abi-arity-mismatch" in self._codes(GOOD_CPP, bad)

    def test_width_mismatch_flagged(self):
        # u64 count bound as c_int: the silent-truncation classic.
        bad = GOOD_PY.replace(
            "L.tmpi_x_push.argtypes = [i32, vp, u64]",
            "L.tmpi_x_push.argtypes = [i32, vp, i32]")
        assert "abi-type-mismatch" in self._codes(GOOD_CPP, bad)

    def test_missing_binding_flagged(self):
        bad = "\n".join(l for l in GOOD_PY.splitlines()
                        if "tmpi_x_push" not in l)
        assert "abi-missing-binding" in self._codes(GOOD_CPP, bad)

    def test_undeclared_symbol_flagged(self):
        bad = GOOD_PY + "\nL.tmpi_x_gone.argtypes = [i32]\n" \
                        "L.tmpi_x_gone.restype = i32\n"
        assert "abi-undeclared-symbol" in self._codes(GOOD_CPP, bad)

    def test_called_but_undeclared_flagged(self):
        bad = "\n".join(l for l in GOOD_PY.splitlines()
                        if "tmpi_x_free" not in l) + "\nL.tmpi_x_free(3)\n"
        codes = self._codes(GOOD_CPP, bad)
        assert "abi-call-undeclared" in codes

    def test_missing_restype_flagged(self):
        bad = GOOD_PY.replace("L.tmpi_x_count.restype = u64\n", "")
        assert "abi-missing-restype" in self._codes(GOOD_CPP, bad)

    def test_void_restype_default_flagged(self):
        # void fn left on ctypes' default c_int restype.
        bad = GOOD_PY.replace("L.tmpi_x_free.restype = None\n", "")
        assert "abi-missing-restype" in self._codes(GOOD_CPP, bad)

    def test_repo_tree_clean(self):
        assert [str(f) for f in abi.check_repo(REPO)] == []


# ------------------------------------------------------------------ knobs

class TestKnobChecker:
    FIELDS = ["hc_alpha", "ps_beta", "plain_gamma"]
    SOURCES = {
        "torchmpi_tpu/collectives/hostcomm.py":
            'x = config.get("hc_alpha")',
        "torchmpi_tpu/parameterserver/native.py":
            'y = config.get("ps_beta")',
        "torchmpi_tpu/other.py": 'z = config.get("plain_gamma")',
    }
    DOCS = {"docs/config.md": "`hc_alpha` `ps_beta` `plain_gamma`"}

    def _codes(self, fields=None, sources=None, docs=None):
        return [f.code for f in knobs.check_knobs(
            fields or self.FIELDS, sources or self.SOURCES,
            docs or self.DOCS)]

    def test_clean_set_is_silent(self):
        assert self._codes() == []

    def test_unread_knob_flagged(self):
        assert "knobs-unread" in self._codes(
            fields=self.FIELDS + ["plain_unread"],
            docs={"docs/config.md":
                  "`hc_alpha` `ps_beta` `plain_gamma` `plain_unread`"})

    def test_undocumented_knob_flagged(self):
        assert "knobs-undocumented" in self._codes(
            docs={"docs/config.md": "`hc_alpha` `ps_beta`"})

    def test_unplumbed_hc_knob_flagged(self):
        # read somewhere, but not by the hostcomm binding module
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/collectives/hostcomm.py"] = "pass"
        srcs["torchmpi_tpu/elsewhere.py"] = 'x = config.get("hc_alpha")'
        assert "knobs-unplumbed" in self._codes(sources=srcs)

    def test_documented_nonexistent_knob_flagged(self):
        docs = dict(self.DOCS)
        docs["docs/failure.md"] = "tune `ps_nonexistent_knob` for this"
        assert "knobs-doc-nonexistent" in self._codes(docs=docs)

    def test_unplumbed_data_knob_flagged(self):
        # Seeded-bad fixture for the data_ namespace: the knob is read
        # SOMEWHERE, but not by data/pipeline.py — the pipeline's single
        # knob reader never sees it, so the stages run blind to it.
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/engine/sgdengine.py"] = \
            'x = config.get("data_q")'
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `data_q`"}
        codes = self._codes(fields=self.FIELDS + ["data_q"],
                            sources=srcs, docs=docs)
        assert "knobs-unplumbed" in codes

    def test_plumbed_data_knob_clean(self):
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/data/pipeline.py"] = \
            'x = config.get("data_q")'
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `data_q`"}
        assert self._codes(fields=self.FIELDS + ["data_q"],
                           sources=srcs, docs=docs) == []

    def test_nonexistent_data_doc_token_flagged(self):
        docs = dict(self.DOCS)
        docs["docs/data.md"] = "tune `data_nonexistent_knob` for this"
        assert "knobs-doc-nonexistent" in self._codes(docs=docs)

    def test_unplumbed_numerics_knob_flagged(self):
        # Seeded-bad fixture for the numerics_ namespace: the knob is
        # read and documented, but obs/numerics.py (numerics_config, the
        # single reader the engine/auditor/history consult) never quotes
        # it — the plane runs blind to it.
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/elsewhere.py"] = 'x = config.get("numerics_q")'
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `numerics_q`"}
        codes = self._codes(fields=self.FIELDS + ["numerics_q"],
                            sources=srcs, docs=docs)
        assert "knobs-unplumbed" in codes

    def test_plumbed_numerics_knob_clean(self):
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/obs/numerics.py"] = (
            'x = config.get("numerics_q")')
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `numerics_q`"}
        assert self._codes(fields=self.FIELDS + ["numerics_q"],
                           sources=srcs, docs=docs) == []

    def test_nonexistent_numerics_doc_token_flagged(self):
        docs = dict(self.DOCS)
        docs["docs/numerics.md"] = "tune `numerics_nonexistent` for this"
        assert "knobs-doc-nonexistent" in self._codes(docs=docs)

    def test_unplumbed_journal_knob_flagged(self):
        # Seeded-bad fixture for the journal_ namespace: the knob is
        # read and documented, but obs/journal.py (journal_config, the
        # single reader every emit site consults) never quotes it.
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/elsewhere.py"] = 'x = config.get("journal_q")'
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `journal_q`"}
        codes = self._codes(fields=self.FIELDS + ["journal_q"],
                            sources=srcs, docs=docs)
        assert "knobs-unplumbed" in codes

    def test_plumbed_journal_knob_clean(self):
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/obs/journal.py"] = (
            'x = config.get("journal_q")')
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `journal_q`"}
        assert self._codes(fields=self.FIELDS + ["journal_q"],
                           sources=srcs, docs=docs) == []

    def test_unplumbed_history_knob_flagged(self):
        # Same for the history_ namespace and obs/history.py
        # (history_config, the sampler's single reader).
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/elsewhere.py"] = 'x = config.get("history_q")'
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `history_q`"}
        codes = self._codes(fields=self.FIELDS + ["history_q"],
                            sources=srcs, docs=docs)
        assert "knobs-unplumbed" in codes

    def test_nonexistent_journal_doc_token_flagged(self):
        docs = dict(self.DOCS)
        docs["docs/history.md"] = "tune `journal_nonexistent` for this"
        assert "knobs-doc-nonexistent" in self._codes(docs=docs)

    def test_unplumbed_autotune_knob_flagged(self):
        # Seeded-bad fixture for the autotune_ namespace: the knob is
        # read SOMEWHERE, but not by collectives/autotune.py — the
        # autotuner itself never sees it.
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/elsewhere.py"] = 'x = config.get("autotune_q")'
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `autotune_q`"}
        codes = self._codes(fields=self.FIELDS + ["autotune_q"],
                            sources=srcs, docs=docs)
        assert "knobs-unplumbed" in codes

    def test_nonexistent_autotune_doc_token_flagged(self):
        docs = dict(self.DOCS)
        docs["docs/autotune.md"] = "set `autotune_nonexistent` to tune"
        assert "knobs-doc-nonexistent" in self._codes(docs=docs)

    def test_unplumbed_resize_knob_flagged(self):
        # Seeded-bad fixture for the resize_ namespace: the knob is read
        # SOMEWHERE, but not by runtime/resize.py (resize_config, the
        # protocol's single reader) — the state machine runs blind to it.
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/elsewhere.py"] = 'x = config.get("resize_q")'
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `resize_q`"}
        codes = self._codes(fields=self.FIELDS + ["resize_q"],
                            sources=srcs, docs=docs)
        assert "knobs-unplumbed" in codes

    def test_plumbed_scale_knob_clean(self):
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/runtime/resize.py"] = (
            'x = config.get("scale_q")')
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `scale_q`"}
        assert self._codes(fields=self.FIELDS + ["scale_q"],
                           sources=srcs, docs=docs) == []

    def test_nonexistent_resize_doc_token_flagged(self):
        docs = dict(self.DOCS)
        docs["docs/resize.md"] = "arm `resize_nonexistent` before this"
        assert "knobs-doc-nonexistent" in self._codes(docs=docs)

    def test_unplumbed_alert_knob_flagged(self):
        # Seeded-bad fixture for the alert_ namespace: the knob is read
        # SOMEWHERE, but not by obs/alerts.py (alerts_config, the single
        # reader the engine builder / sampler hook / route consult) —
        # the alert plane runs blind to it.
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/elsewhere.py"] = 'x = config.get("alert_q")'
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `alert_q`"}
        codes = self._codes(fields=self.FIELDS + ["alert_q"],
                            sources=srcs, docs=docs)
        assert "knobs-unplumbed" in codes

    def test_plumbed_alert_knob_clean(self):
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/obs/alerts.py"] = 'x = config.get("alert_q")'
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `alert_q`"}
        assert self._codes(fields=self.FIELDS + ["alert_q"],
                           sources=srcs, docs=docs) == []

    def test_nonexistent_alert_doc_token_flagged(self):
        docs = dict(self.DOCS)
        docs["docs/alerts.md"] = "tune `alert_nonexistent` for this"
        assert "knobs-doc-nonexistent" in self._codes(docs=docs)

    def test_unplumbed_retune_knob_flagged(self):
        # Seeded-bad fixture for the retune_ namespace: the knob is read
        # SOMEWHERE, but not by collectives/retune.py (retune_config,
        # the controller's single reader) — the debounce/cooldown/revert
        # lifecycle runs blind to it.
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/elsewhere.py"] = 'x = config.get("retune_q")'
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `retune_q`"}
        codes = self._codes(fields=self.FIELDS + ["retune_q"],
                            sources=srcs, docs=docs)
        assert "knobs-unplumbed" in codes

    def test_plumbed_retune_knob_clean(self):
        srcs = dict(self.SOURCES)
        srcs["torchmpi_tpu/collectives/retune.py"] = (
            'x = config.get("retune_q")')
        docs = {"docs/config.md":
                "`hc_alpha` `ps_beta` `plain_gamma` `retune_q`"}
        assert self._codes(fields=self.FIELDS + ["retune_q"],
                           sources=srcs, docs=docs) == []

    def test_nonexistent_retune_doc_token_flagged(self):
        docs = dict(self.DOCS)
        docs["docs/autotune.md"] = "raise `retune_nonexistent` to slow it"
        assert "knobs-doc-nonexistent" in self._codes(docs=docs)

    def test_repo_tree_clean(self):
        assert [str(f) for f in knobs.check_repo(REPO)] == []


# ------------------------------------------------------------------ jaxpr

def _mesh2(name="tp"):
    return Mesh(np.array(jax.devices()[:2]), (name,))


class TestJaxprLint:
    def test_clean_manual_psum_silent(self):
        mesh = _mesh2()
        fn = shard_map(lambda x: jax.lax.psum(x, "tp"), mesh=mesh,
                       in_specs=P("tp"), out_specs=P(), check_vma=False)
        x = jnp.ones((2, 8), jnp.bfloat16)
        findings, notes = jaxpr_lint.lint_callable(
            fn, (x,), "fixture-clean", expected_wire="bfloat16")
        assert findings == [] and notes == []

    def test_unbound_axis_caught(self):
        mesh = _mesh2()
        fn = shard_map(lambda x: jax.lax.psum(x, "nope"), mesh=mesh,
                       in_specs=P("tp"), out_specs=P(), check_vma=False)
        findings, _ = jaxpr_lint.lint_callable(
            fn, (jnp.ones((2, 8)),), "fixture-unbound")
        assert [f.code for f in findings] == ["jaxpr-unbound-axis"]

    def test_wire_dtype_upcast_caught(self):
        # f32 psum in a manual region while the gate resolves bf16: the
        # accidental-reupcast regression the pass pins.
        mesh = _mesh2()
        fn = shard_map(
            lambda x: jax.lax.psum(x.astype(jnp.float32), "tp"),
            mesh=mesh, in_specs=P("tp"), out_specs=P(), check_vma=False)
        findings, _ = jaxpr_lint.lint_callable(
            fn, (jnp.ones((2, 8), jnp.bfloat16),), "fixture-wire",
            expected_wire="bfloat16")
        assert [f.code for f in findings] == ["jaxpr-manual-psum-wire-dtype"]

    def test_scalar_psum_exempt_from_wire_check(self):
        mesh = _mesh2()
        fn = shard_map(
            lambda x: jax.lax.psum(jnp.sum(x).astype(jnp.float32), "tp"),
            mesh=mesh, in_specs=P("tp"), out_specs=P(), check_vma=False)
        findings, _ = jaxpr_lint.lint_callable(
            fn, (jnp.ones((2, 8), jnp.bfloat16),), "fixture-scalar",
            expected_wire="bfloat16")
        assert findings == []

    def test_collective_under_cond_caught(self):
        mesh = _mesh2()

        def body(x):
            return jax.lax.cond(x.sum() > 0,
                                lambda v: jax.lax.psum(v, "tp"),
                                lambda v: v, x)

        fn = shard_map(body, mesh=mesh, in_specs=P("tp"), out_specs=P("tp"),
                       check_vma=False)
        findings, _ = jaxpr_lint.lint_callable(
            fn, (jnp.ones((2, 8), jnp.bfloat16),), "fixture-cond",
            expected_wire="bfloat16")
        assert "jaxpr-collective-under-cond" in [f.code for f in findings]

    def test_suppression_silences_and_counts(self):
        mesh = _mesh2()

        def body(x):
            return jax.lax.cond(x.sum() > 0,
                                lambda v: jax.lax.psum(v, "tp"),
                                lambda v: v, x)

        fn = shard_map(body, mesh=mesh, in_specs=P("tp"), out_specs=P("tp"),
                       check_vma=False)
        sup = jaxpr_lint.Suppression(
            program="fixture-sup", code="jaxpr-collective-under-cond",
            rationale="fixture: predicate is a trace-time constant")
        findings, notes = jaxpr_lint.lint_callable(
            fn, (jnp.ones((2, 8), jnp.bfloat16),), "fixture-sup",
            expected_wire="bfloat16", suppressions=[sup])
        assert findings == []
        assert sup.hits == 1 and len(notes) == 1

    def test_full_program_registry_clean(self):
        # The FULL analyzer surface over every registered program —
        # tracing is seconds once jax is warm, so this is tier-1, and a
        # wire-dtype upcast or a fresh under-cond collective in any
        # multi-chip program fails CI here.  Only a failed topology
        # ENVIRONMENT probe may skip; a crash in the linter itself must
        # fail (a broad skip would silently disable the gate).
        from torchmpi_tpu.runtime import topology

        try:
            topology.topology_devices("v5e-8")
        except Exception as e:  # noqa: BLE001 — no libtpu in this install
            pytest.skip(f"topology environment unavailable: {e!r}")
        findings, notes = jaxpr_lint.lint_registered_programs()
        assert [str(f) for f in findings] == []
        # the two accepted-hazard classes stay visible as notes, never
        # silently widening: CE f32 forward psums + 1F1B under-cond.
        assert {n.code for n in notes} == {
            "suppressed:jaxpr-collective-under-cond",
            "suppressed:jaxpr-manual-psum-wire-dtype"}


# ------------------------------------------------------------------ locks

LOCKS_CLEAN = """
import threading
A = threading.Lock()
B = threading.Lock()

def f():
    with A:
        with B:
            pass

def g():
    with A:
        with B:
            pass
"""

LOCKS_CYCLE = LOCKS_CLEAN + """
def h():
    with B:
        with A:
            pass
"""

LOCKS_BLOCKING = """
import threading
import time
L = threading.Lock()

def f():
    with L:
        time.sleep(1.0)
"""


class TestLocksPass:
    def _codes(self, text, sups=()):
        findings, _ = locks.check_lock_sources({"m.py": text}, list(sups))
        return [f.code for f in findings]

    def test_consistent_order_silent(self):
        assert self._codes(LOCKS_CLEAN) == []

    def test_lock_order_cycle_flagged(self):
        assert "locks-order-cycle" in self._codes(LOCKS_CYCLE)

    def test_blocking_call_under_lock_flagged(self):
        assert self._codes(LOCKS_BLOCKING) == ["locks-blocking-under-lock"]

    def test_suppression_silences_and_counts(self):
        sup = locks.Suppression(
            code="locks-blocking-under-lock", where="m.py",
            rationale="fixture: the sleep is the lock's whole point")
        findings, notes = locks.check_lock_sources(
            {"m.py": LOCKS_BLOCKING}, [sup])
        assert findings == []
        assert sup.hits == 1
        assert [n.code for n in notes] == \
            ["suppressed:locks-blocking-under-lock"]

    def test_stale_suppression_flagged(self):
        sup = locks.Suppression(
            code="locks-blocking-under-lock", where="nowhere.py",
            rationale="fixture: matches nothing")
        assert self._codes(LOCKS_CLEAN, [sup]) == ["locks-stale-suppression"]

    def test_repo_tree_clean(self):
        findings, _ = locks.check_repo(REPO)
        assert [str(f) for f in findings] == []


# ---------------------------------------------------------------- threads

THREAD_UNJOINED = """
import threading

class W:
    def __init__(self):
        self.t = threading.Thread(target=self.run)
        self.t.start()
"""

THREAD_DAEMON = """
import threading

class W:
    def __init__(self):
        self.t = threading.Thread(target=self.run, daemon=True)
        self.t.start()
"""

TIMER_UNSTOPPED = """
import threading

class W:
    def __init__(self):
        self.t = threading.Timer(5.0, self.fire)
        self.t.start()
"""

QUEUE_UNBOUNDED = """
import queue
import threading

class W:
    def __init__(self):
        self.q = queue.Queue()
        threading.Thread(target=self.drain, daemon=True).start()
"""


class TestThreadsPass:
    def _codes(self, text, sups=()):
        findings, _ = threads.check_thread_sources({"m.py": text},
                                                   list(sups))
        return [f.code for f in findings]

    def test_unjoined_thread_flagged(self):
        assert self._codes(THREAD_UNJOINED) == ["threads-unjoined-thread"]

    def test_daemon_thread_clean(self):
        assert self._codes(THREAD_DAEMON) == []

    def test_joined_thread_clean(self):
        joined = THREAD_UNJOINED + """
    def stop(self):
        self.t.join()
"""
        assert self._codes(joined) == []

    def test_unstopped_timer_flagged(self):
        assert self._codes(TIMER_UNSTOPPED) == ["threads-unstopped-timer"]

    def test_cancelled_timer_clean(self):
        cancelled = TIMER_UNSTOPPED + """
    def close(self):
        self.t.cancel()
"""
        assert self._codes(cancelled) == []

    def test_unbounded_queue_flagged(self):
        assert self._codes(QUEUE_UNBOUNDED) == ["threads-unbounded-channel"]

    def test_bounded_queue_clean(self):
        bounded = QUEUE_UNBOUNDED.replace("queue.Queue()",
                                          "queue.Queue(maxsize=64)")
        assert self._codes(bounded) == []

    def test_stale_suppression_flagged(self):
        sup = locks.Suppression(
            code="threads-unbounded-channel", where="nowhere.py",
            rationale="fixture: matches nothing")
        assert self._codes(THREAD_DAEMON, [sup]) == \
            ["threads-stale-suppression"]

    def test_repo_tree_clean(self):
        findings, _ = threads.check_repo(REPO)
        assert [str(f) for f in findings] == []


# --------------------------------------------------------------- registry

class TestRegistryPass:
    METRICS = {"tmpi_x_total": {"kind": "counter", "where": "m.py:1"},
               "tmpi_x_depth": {"kind": "gauge", "where": "m.py:2"}}
    DOCS = {"docs/x.md": "`tmpi_x_total` and `tmpi_x_depth`"}
    RULES = [{"name": "r", "kind": "movement", "metric": "tmpi_x_total"}]
    KINDS = {"x.done": "m.py:9"}
    RCA = ["x.done"]

    def _codes(self, **kw):
        kw.setdefault("metrics", self.METRICS)
        kw.setdefault("docs", self.DOCS)
        kw.setdefault("alert_rules", self.RULES)
        kw.setdefault("journal_kinds", self.KINDS)
        kw.setdefault("rca_kinds", self.RCA)
        # fixtures carry their own tiny taxonomy, not the repo's
        kw.setdefault("informational", {})
        findings, _ = registry.check_registry(**kw)
        return [f.code for f in findings]

    def test_clean_set_is_silent(self):
        assert self._codes() == []

    def test_counter_without_total_suffix_flagged(self):
        m = dict(self.METRICS)
        m["tmpi_x_hits"] = {"kind": "counter", "where": "m.py:3"}
        docs = {"docs/x.md": self.DOCS["docs/x.md"] + " `tmpi_x_hits`"}
        assert "registry-bad-metric-name" in self._codes(metrics=m,
                                                         docs=docs)

    def test_unprefixed_metric_flagged(self):
        m = dict(self.METRICS)
        m["rogue_total"] = {"kind": "counter", "where": "m.py:3"}
        assert "registry-bad-metric-name" in self._codes(metrics=m)

    def test_undocumented_metric_flagged(self):
        m = dict(self.METRICS)
        m["tmpi_x_ghost_total"] = {"kind": "counter", "where": "m.py:3"}
        assert "registry-undocumented-metric" in self._codes(metrics=m)

    def test_doc_stale_metric_flagged(self):
        docs = {"docs/x.md":
                self.DOCS["docs/x.md"] + " plus `tmpi_gone_total`"}
        assert "registry-doc-stale-metric" in self._codes(docs=docs)

    def test_alert_unknown_metric_flagged(self):
        rules = self.RULES + [{"name": "dead", "kind": "threshold",
                               "metric": "tmpi_never_emitted"}]
        assert "registry-alert-unknown-metric" in self._codes(
            alert_rules=rules)

    def test_orphan_journal_kind_flagged(self):
        kinds = dict(self.KINDS)
        kinds["x.orphan"] = "m.py:11"
        assert "registry-orphan-journal-kind" in self._codes(
            journal_kinds=kinds)

    def test_informational_kind_is_note_not_finding(self):
        kinds = dict(self.KINDS)
        kinds["x.fyi"] = "m.py:11"
        assert self._codes(journal_kinds=kinds,
                           informational={"x.fyi": "operator trivia"}) == []

    def test_rca_stale_kind_flagged(self):
        assert "registry-rca-stale-kind" in self._codes(
            rca_kinds=self.RCA + ["never.emitted"])

    def test_stale_informational_flagged(self):
        assert "registry-stale-informational" in self._codes(
            informational={"x.never": "registered but never emitted"})

    def test_repo_tree_clean(self):
        findings, _ = registry.check_repo(REPO)
        assert [str(f) for f in findings] == []


# ------------------------------------------------------------------- wire

WIRE_CPP_OPS = """
enum class PsTraceOp : uint8_t { kTOpCreate = 1, kTOpFree = 2 };
"""

WIRE_PY_OPS_GOOD = 'PS_OPS = {1: "create", 2: "free"}\n'


class TestWirePass:
    def _codes(self, **kw):
        kw.setdefault("cpp_ps", "")
        kw.setdefault("cpp_hc", "")
        kw.setdefault("py_obs_native", "")
        kw.setdefault("py_ps_native", "")
        kw.setdefault("py_hostcomm", "")
        kw.setdefault("py_serve", "")
        kw.setdefault("callers", {})
        kw.setdefault("docs", {})
        findings, _ = wire.check_wire_sources(**kw)
        return [f.code for f in findings]

    def test_matching_mirror_silent(self):
        assert self._codes(cpp_ps=WIRE_CPP_OPS,
                           py_obs_native=WIRE_PY_OPS_GOOD) == []

    def test_opcode_mismatch_flagged(self):
        bad = WIRE_PY_OPS_GOOD.replace('2: "free"', '3: "free"')
        assert self._codes(cpp_ps=WIRE_CPP_OPS, py_obs_native=bad) == \
            ["wire-opcode-mismatch"]

    def test_missing_mirror_flagged(self):
        bad = 'PS_OPS = {1: "create"}\n'
        assert self._codes(cpp_ps=WIRE_CPP_OPS, py_obs_native=bad) == \
            ["wire-missing-mirror"]

    def test_extra_mirror_flagged(self):
        bad = WIRE_PY_OPS_GOOD.replace('}', ', 9: "phantom"}')
        assert self._codes(cpp_ps=WIRE_CPP_OPS, py_obs_native=bad) == \
            ["wire-extra-mirror"]

    def test_duplicate_discriminator_value_flagged(self):
        cpp = ("constexpr uint32_t kAckOk = 1;\n"
               "constexpr uint32_t kAckRetry = 1;\n")
        assert "wire-duplicate-value" in self._codes(cpp_ps=cpp)

    def test_doc_stale_constant_flagged(self):
        docs = {"docs/x.md": "frames start with `kNonexistentMagic`"}
        assert self._codes(docs=docs) == ["wire-doc-stale-constant"]

    def test_route_undocumented_flagged(self):
        serve = ('class H:\n'
                 '    def do_GET(self):\n'
                 '        if self.path == "/stats":\n'
                 '            return\n')
        assert self._codes(py_serve=serve) == ["wire-route-undocumented"]

    def test_documented_route_silent(self):
        serve = ('class H:\n'
                 '    def do_GET(self):\n'
                 '        if self.path == "/stats":\n'
                 '            return\n')
        docs = {"docs/x.md": "scrape `GET /stats` for the table"}
        assert self._codes(py_serve=serve, docs=docs) == []

    def test_route_unserved_flagged(self):
        callers = {"c.py": 'PATH = "/gone"\n'}
        assert self._codes(callers=callers) == ["wire-route-unserved"]

    def test_doc_stale_route_flagged(self):
        docs = {"docs/x.md": "poll `GET /ghost` for status"}
        assert self._codes(docs=docs) == ["wire-doc-stale-route"]

    def test_route_404_drift_flagged(self):
        serve = ('class H:\n'
                 '    def do_GET(self):\n'
                 '        if self.path == "/a":\n'
                 '            return\n'
                 '        self.reply(404, ["/a", "/b", "/c"])\n')
        docs = {"docs/x.md": "`/a` `/b` `/c`"}
        codes = self._codes(py_serve=serve, docs=docs)
        # /b and /c advertised in the 404 help body but never dispatched
        assert codes.count("wire-route-404-drift") == 2

    def test_repo_tree_clean(self):
        findings, _ = wire.check_repo(REPO)
        assert [str(f) for f in findings] == []


# ---------------------------------------------------------------- verdict

class TestAnalyzeArtifact:
    """Pins ANALYZE_r18.json — the committed whole-tree verdict.  The
    doc-contract drift the passes caught live (undocumented metrics in
    observability/numerics docs, a stale `per_second` token, the
    undocumented /health alias) is regression-pinned by the clean-tree
    tests above: reintroducing any of it flips a `test_repo_tree_clean`."""

    def test_artifact_verdict_pinned(self):
        import json

        artifact = json.loads((REPO / "ANALYZE_r18.json").read_text())
        assert artifact["verdict"] == "PASS"
        assert set(artifact["passes"]) == {
            "abi", "knobs", "locks", "threads", "registry", "wire", "jaxpr"}
        assert artifact["findings"] == []
        # every suppression is a reviewed exception with a written WHY
        sups = artifact["suppressions"]
        assert sups, "suppression inventory missing"
        assert {s["pass"] for s in sups} >= {"locks", "threads", "registry",
                                             "jaxpr"}
        for s in sups:
            assert s["rationale"].strip(), s

    def test_inventory_matches_live_modules(self):
        from torchmpi_tpu.analysis.__main__ import suppression_inventory

        import json

        artifact = json.loads((REPO / "ANALYZE_r18.json").read_text())
        # the artifact went through JSON (tuples -> lists); compare in
        # that normal form
        live = json.loads(json.dumps(suppression_inventory()))
        assert artifact["suppressions"] == live, (
            "ANALYZE_r18.json is stale — regenerate with "
            "python -m torchmpi_tpu.analysis --json")


# ---------------------------------------------------------- CLI and drill

class TestCliFast:
    def test_abi_knobs_cli_clean_and_fixture_exit_codes(self):
        from torchmpi_tpu.analysis.__main__ import main

        # clean tree, cheap passes only -> exit 0
        assert main(["--passes", "abi,knobs", "--repo", str(REPO),
                     "-q"]) == 0

    def test_all_seven_passes_in_process_exit_zero(self):
        # The whole-tree contract: every pass, one process, exit 0.  The
        # jaxpr pass traces the program registry, so mirror its only
        # legitimate skip (no topology environment); everything else —
        # including a crash inside any analyzer — must FAIL here.
        from torchmpi_tpu.analysis.__main__ import main
        from torchmpi_tpu.runtime import topology

        try:
            topology.topology_devices("v5e-8")
        except Exception as e:  # noqa: BLE001 — no libtpu in this install
            pytest.skip(f"topology environment unavailable: {e!r}")
        assert main(["--repo", str(REPO), "-q"]) == 0


@pytest.mark.slow
class TestCliFull:
    def test_full_analyzer_subprocess_exits_zero(self):
        out = subprocess.run(
            [sys.executable, "-m", "torchmpi_tpu.analysis"],
            cwd=REPO, capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
        assert "0 finding(s)" in out.stdout


@pytest.mark.slow
class TestSanitizeDrill:
    def test_quick_drill_in_process(self, tmp_path):
        sys.path.insert(0, str(REPO / "scripts"))
        try:
            import sanitize_drill
        finally:
            sys.path.pop(0)
        out = tmp_path / "SANITIZE_test.json"
        sanitize_drill.main(["--quick", "--out", str(out)])
        import json

        artifact = json.loads(out.read_text())
        assert artifact["verdict"] == "PASS"
        assert artifact["total_unsuppressed_findings"] == 0
        assert {l["leg"] for l in artifact["legs"]} == {"tsan", "asan"}
        # every suppression carries a written rationale
        for s in artifact["suppressions"]:
            assert s["rationale"].strip(), s
