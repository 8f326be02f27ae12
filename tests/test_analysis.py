"""The static auditor must catch every hazard it claims to (one deliberate
fixture per ESSR code) and must find the shipped tree clean."""
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (
    RULES,
    Report,
    Violation,
    audit_jaxpr,
    check_recompile,
    lint_source,
    run_ast_lint,
    run_jaxpr_audit,
)

REPO_ROOT = __file__.rsplit("/tests/", 1)[0]


def codes(violations):
    return {v.code for v in violations}


# ---------------------------------------------------------------------------
# jaxpr audit fixtures (ESSR1xx) — each builds a graph with exactly the
# hazard its rule exists to catch
# ---------------------------------------------------------------------------

def test_essr101_host_callback_detected():
    def f(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2.0,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1.0

    closed = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    vs = audit_jaxpr(closed, "fixture.callback")
    assert "ESSR101" in codes(vs)


def test_essr102_weak_typed_output_detected():
    # a bare python-scalar graph stays weak-typed end to end
    closed = jax.make_jaxpr(lambda t: t + 1.0)(1.0)
    vs = audit_jaxpr(closed, "fixture.weak")
    assert "ESSR102" in codes(vs)
    assert any("weak" in v.message for v in vs)


def test_essr102_wide_dtype_detected():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0
        )(jnp.ones((4,), jnp.float32))
    vs = audit_jaxpr(closed, "fixture.f64")
    assert "ESSR102" in codes(vs)
    assert any("float64" in v.message for v in vs)


def test_essr103_nonunique_set_scatter_detected():
    def f(x, i):
        return x.at[i].set(1.0)          # set-scatter, indices not unique

    closed = jax.make_jaxpr(f)(jnp.zeros((8,), jnp.float32),
                               jnp.array([0, 0, 1]))
    vs = audit_jaxpr(closed, "fixture.scatter")
    assert "ESSR103" in codes(vs)


def test_essr103_clean_when_guaranteed():
    def f(x, i):
        return x.at[i].set(1.0, unique_indices=True, mode="drop")

    closed = jax.make_jaxpr(f)(jnp.zeros((8,), jnp.float32),
                               jnp.array([0, 1, 2]))
    assert "ESSR103" not in codes(audit_jaxpr(closed, "fixture.scatter_ok"))


def test_essr104_oversized_constant_detected():
    baked = jnp.zeros((64, 64), jnp.float32)        # 16 KiB closed over
    closed = jax.make_jaxpr(lambda x: x + baked)(jnp.zeros((64, 64)))
    vs = audit_jaxpr(closed, "fixture.const", const_budget=1024)
    assert "ESSR104" in codes(vs)
    assert "ESSR104" not in codes(
        audit_jaxpr(closed, "fixture.const", const_budget=1 << 20))


def test_essr105_static_threshold_recompile_detected():
    # the anti-pattern ExecutionPlan forbids: a threshold as a static arg
    @jax.jit
    def good(x, t):
        return jnp.where(x > t, x, 0.0)

    leaky = jax.jit(lambda x, t: jnp.where(x > t, x, 0.0),
                    static_argnums=(1,))
    x = jnp.arange(4.0)
    assert check_recompile(good, (x, 1.0), (x, 2.0), "fixture.good") == []
    vs = check_recompile(leaky, (x, 1.0), (x, 2.0), "fixture.leaky")
    assert codes(vs) == {"ESSR105"}


# ---------------------------------------------------------------------------
# AST lint fixtures (ESSR2xx) — synthetic modules at rule-scoped relpaths
# ---------------------------------------------------------------------------

def test_essr201_free_entry_point_detected():
    src = textwrap.dedent("""
        def run_inference(params, frame, cfg):
            return frame
    """)
    vs = lint_source(src, "src/repro/core/newmode.py")
    assert "ESSR201" in codes(vs)
    # same function is legal inside the api package, or when private
    assert lint_source(src, "src/repro/api/newmode.py") == []
    assert "ESSR201" not in codes(lint_source(
        src.replace("run_inference", "_run_inference"),
        "src/repro/core/newmode.py"))


def test_essr201_suppression_marker():
    src = textwrap.dedent("""
        # essr: allow[ESSR201] — grandfathered
        def run_inference(params, frame, cfg):
            return frame
    """)
    assert lint_source(src, "src/repro/core/legacy.py") == []


def test_essr206_stream_entry_point_detected():
    src = textwrap.dedent("""
        def serve_many(params, streams, cfg):
            return streams
    """)
    vs = lint_source(src, "src/repro/runtime/newmux.py")
    assert "ESSR206" in codes(vs)
    # an engine-riding free function is the same hazard
    assert "ESSR206" in codes(lint_source(textwrap.dedent("""
        def multiplex(engine, frame_streams):
            return frame_streams
    """), "src/repro/runtime/newmux.py"))
    # legal inside the api package, when private, or as a method
    assert lint_source(src, "src/repro/api/newmux.py") == []
    assert "ESSR206" not in codes(lint_source(
        src.replace("serve_many", "_serve_many"),
        "src/repro/runtime/newmux.py"))
    assert "ESSR206" not in codes(lint_source(textwrap.dedent("""
        class Mux:
            def serve(self, params, streams):
                return streams
    """), "src/repro/runtime/newmux.py"))
    # a stream bundle without params/engine is not a serving entry point
    assert "ESSR206" not in codes(lint_source(textwrap.dedent("""
        def zip_streams(streams):
            return streams
    """), "src/repro/runtime/newmux.py"))


def test_essr202_numpy_in_traced_body_detected():
    src = textwrap.dedent("""
        import numpy as np
        import jax

        @jax.jit
        def fwd(x):
            return np.asarray(x) + 1
    """)
    vs = lint_source(src, "src/repro/core/bad.py")
    assert "ESSR202" in codes(vs)
    # out of scope outside core/ and kernels/
    assert "ESSR202" not in codes(lint_source(src, "src/repro/api/ok.py"))
    # host-side helpers (never traced) are allowed to use numpy
    host = src.replace("@jax.jit\n", "")
    assert "ESSR202" not in codes(lint_source(host, "src/repro/core/ok.py"))


def test_essr203_time_in_traced_body_detected():
    src = textwrap.dedent("""
        import time
        import jax

        def body(x):
            t0 = time.perf_counter()
            return x * t0

        f = jax.jit(body)
    """)
    vs = lint_source(src, "src/repro/kernels/bad.py")
    assert "ESSR203" in codes(vs)


def test_essr204_host_sync_in_traced_body_detected():
    src = textwrap.dedent("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n",))
        def fwd(x, n):
            y = (x * n).block_until_ready()
            return jax.device_get(y)
    """)
    vs = [v for v in lint_source(src, "src/repro/core/bad.py")
          if v.code == "ESSR204"]
    assert len(vs) == 2                  # both the sync and the transfer


def test_essr205_mutable_frozen_field_detected():
    src = textwrap.dedent("""
        import dataclasses
        from typing import List, Tuple

        @dataclasses.dataclass(frozen=True)
        class Plan:
            caps: List[int]
            name: str

        @dataclasses.dataclass(frozen=True)
        class GoodPlan:
            caps: Tuple[int, ...]

        @dataclasses.dataclass(frozen=True, eq=False)
        class IdentityHashed:
            caps: List[int]

        @dataclasses.dataclass
        class Mutable:
            caps: List[int]
    """)
    vs = [v for v in lint_source(src, "src/repro/api/plans.py")
          if v.code == "ESSR205"]
    assert len(vs) == 1                  # only Plan.caps: frozen + eq
    assert "Plan" in vs[0].message


def test_essr207_swallowed_exception_detected():
    src = textwrap.dedent("""
        def tick(streams):
            out = []
            for s in streams:
                try:
                    out.append(next(s))
                except Exception:
                    pass
            return out
    """)
    vs = lint_source(src, "src/repro/runtime/mux.py")
    assert codes(vs) == {"ESSR207"}
    assert "swallows" in vs[0].message
    # bare except and BaseException are equally broad
    assert "ESSR207" in codes(lint_source(
        src.replace("except Exception:", "except:"),
        "src/repro/runtime/mux.py"))
    assert "ESSR207" in codes(lint_source(
        src.replace("Exception", "BaseException"),
        "src/repro/api/serve.py"))


def test_essr207_recovery_and_scope():
    recorded = textwrap.dedent("""
        def tick(guard, streams):
            for i, s in enumerate(streams):
                try:
                    next(s)
                except Exception as e:
                    guard.record(i, "retire", repr(e))
    """)
    assert lint_source(recorded, "src/repro/runtime/mux.py") == []
    reraised = textwrap.dedent("""
        def tick(s):
            try:
                return next(s)
            except Exception:
                raise RuntimeError("tick failed")
    """)
    assert lint_source(reraised, "src/repro/runtime/mux.py") == []
    warned = textwrap.dedent("""
        import warnings
        def load(path):
            try:
                return open(path).read()
            except Exception as e:
                warnings.warn(f"unreadable: {e!r}")
    """)
    assert lint_source(warned, "src/repro/api/loader.py") == []
    # narrow handlers are out of scope even when silent
    narrow = textwrap.dedent("""
        def tick(s):
            try:
                return next(s)
            except StopIteration:
                pass
    """)
    assert lint_source(narrow, "src/repro/runtime/mux.py") == []
    # the rule only patrols the serving path
    swallowing = textwrap.dedent("""
        def probe(x):
            try:
                return x()
            except Exception:
                pass
    """)
    assert "ESSR207" not in codes(lint_source(
        swallowing, "src/repro/core/util.py"))
    # suppression marker works like every other ESSR2xx rule
    waived = textwrap.dedent("""
        def probe(x):
            try:
                return x()
            except Exception:  # essr: allow[ESSR207]
                pass
    """)
    assert lint_source(waived, "src/repro/runtime/probe.py") == []


def test_traced_names_resolved_through_partial_and_pallas():
    src = textwrap.dedent("""
        import functools
        import numpy as np
        import jax.experimental.pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = np.tanh(x_ref[...])

        def launch(x):
            return pl.pallas_call(
                functools.partial(kernel),
                out_shape=x)(x)
    """)
    vs = lint_source(src, "src/repro/kernels/bad.py")
    assert "ESSR202" in codes(vs)


# ---------------------------------------------------------------------------
# report machinery
# ---------------------------------------------------------------------------

def test_violation_rejects_unknown_code():
    with pytest.raises(ValueError):
        Violation("ESSR999", "x:1", "nope")


def test_report_roundtrip_and_baseline_diff(tmp_path):
    r = Report([Violation("ESSR202", "src/repro/core/a.py:3", "np op"),
                Violation("ESSR103", "entrypoint:fused", "scatter")])
    path = str(tmp_path / "report.json")
    r.to_json(path)
    back = Report.from_json(path)
    assert {v.key for v in back.violations} == {v.key for v in r.violations}
    assert back.counts()["ESSR202"] == 1 and back.counts()["ESSR101"] == 0

    # gate semantics: same sites pass, a new site fails, fixes never fail
    assert r.new_vs(back) == []
    grown = Report(r.violations
                   + [Violation("ESSR101", "entrypoint:new", "cb")])
    assert codes(grown.new_vs(back)) == {"ESSR101"}
    assert Report([]).new_vs(back) == []


def test_rule_catalog_covers_all_passes():
    assert len(RULES) == 16
    assert {c[:5] for c in RULES} == {"ESSR1", "ESSR2", "ESSR3"}
    # the registry is the single source: the rendered docs rows and the
    # committed docs catalog both carry every code
    from repro.analysis import rules_markdown
    md = rules_markdown()
    with open(f"{REPO_ROOT}/docs/api.md") as f:
        docs = f.read()
    for code in RULES:
        assert code in md
        assert code in docs, f"{code} missing from docs/api.md catalog"


# ---------------------------------------------------------------------------
# clean tree: the shipped repo audits to zero violations
# ---------------------------------------------------------------------------

def test_shipped_tree_passes_ast_lint():
    assert run_ast_lint(REPO_ROOT) == []


def test_shipped_entry_points_pass_jaxpr_audit():
    assert run_jaxpr_audit() == []
