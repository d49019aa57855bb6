"""ShardLint mutation tests: every analyzer rule must trip on its seeded
violation and stay silent on the known-good twin.

Three tiers:

* lint rules — AST fixtures under ``tests/analysis_fixtures/``: for each
  rule one MUST-FLAG file and one MUST-PASS file (the mutation test of
  the analyzer itself);
* jaxpr-audit rules — deliberate violations built in-process (a
  ``debug_callback`` in a jitted body, an f64 promotion under
  ``enable_x64``, an un-donated large carry, a collective on an
  undeclared axis) and asserted detected;
* retrace sentinel — a cold jit must trip ``assert_no_retrace``, a
  warmed one must not; plus the fast (emulated) twin of the stacked
  rung-segment compile-once contract.

The registered-manifest audit itself must also be green — the same
invocation CI runs.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (AuditSpec, RetraceError, assert_no_retrace,
                            audit_jaxpr, hot_paths, lint_file, lint_source,
                            run_audit, watch_compiles)

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")

# rule id -> (must-flag fixture, must-pass fixture, store_rules)
LINT_CASES = {
    "traced-leak": ("traced_leak_bad.py", "traced_leak_good.py", False),
    "wallclock-in-trace": ("wallclock_bad.py", "wallclock_good.py", False),
    "donated-reuse": ("donated_reuse_bad.py", "donated_reuse_good.py", False),
    "non-atomic-write": ("atomic_write_bad.py", "atomic_write_good.py", True),
    "jit-in-loop": ("jit_in_loop_bad.py", "jit_in_loop_good.py", True),
}


class TestLintRules:
    @pytest.mark.parametrize("rule", sorted(LINT_CASES))
    def test_must_flag(self, rule):
        bad, _, store = LINT_CASES[rule]
        findings = lint_file(os.path.join(FIXTURES, bad), store_rules=store)
        assert any(f.rule == rule for f in findings), (
            f"{bad} seeded a {rule} violation but the rule stayed silent: "
            f"{findings}")

    @pytest.mark.parametrize("rule", sorted(LINT_CASES))
    def test_must_pass(self, rule):
        _, good, store = LINT_CASES[rule]
        findings = lint_file(os.path.join(FIXTURES, good), store_rules=store)
        hits = [f for f in findings if f.rule == rule]
        assert not hits, f"{good} is known-good for {rule} but flagged: {hits}"

    def test_flag_counts_are_exact(self):
        """Every seeded violation is found — not just 'at least one'."""
        findings = lint_file(os.path.join(FIXTURES, "traced_leak_bad.py"),
                             store_rules=False)
        assert sum(f.rule == "traced-leak" for f in findings) == 4
        findings = lint_file(os.path.join(FIXTURES, "wallclock_bad.py"),
                             store_rules=False)
        assert sum(f.rule == "wallclock-in-trace" for f in findings) == 3
        findings = lint_file(os.path.join(FIXTURES, "atomic_write_bad.py"),
                             store_rules=True)
        assert sum(f.rule == "non-atomic-write" for f in findings) == 3

    def test_allowlist_comment_suppresses(self):
        src = ("import jax\n"
               "def f(xs):\n"
               "    for x in xs:\n"
               "        # lint: allow[jit-in-loop] one-off trace for a test\n"
               "        g = jax.jit(lambda v: v + x)\n"
               "    return g\n")
        assert lint_source(src, "allowed.py") == []
        # without the comment the same source flags
        stripped = src.replace(
            "        # lint: allow[jit-in-loop] one-off trace for a test\n",
            "")
        assert any(f.rule == "jit-in-loop"
                   for f in lint_source(stripped, "bare.py"))

    def test_store_rules_scoped_by_path(self):
        src = 'def f(p, d):\n    with open(p, "w") as fh:\n        fh.write(d)\n'
        assert any(f.rule == "non-atomic-write"
                   for f in lint_source(src, "src/repro/checkpoint/x.py"))
        assert lint_source(src, "src/repro/eval/x.py") == []

    def test_syntax_error_is_reported_not_raised(self):
        findings = lint_source("def broken(:\n", "broken.py")
        assert [f.rule for f in findings] == ["syntax-error"]


class TestJaxprAudit:
    def test_host_callback_detected(self):
        @jax.jit
        def noisy(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        closed = jax.make_jaxpr(lambda: noisy(jnp.ones(4)))()
        findings = audit_jaxpr(closed, AuditSpec(), where="t")
        assert any(f.rule == "host-callback" for f in findings)
        # the same path with one declared callback passes
        assert audit_jaxpr(closed, AuditSpec(allow_callbacks=1),
                           where="t") == []

    def test_f64_promotion_detected(self):
        with jax.enable_x64(True):
            def promoting(x):
                return x.astype(jnp.float64).sum()

            closed = jax.make_jaxpr(
                lambda: promoting(jnp.ones(4, jnp.float32)))()
        findings = audit_jaxpr(closed, AuditSpec(), where="t")
        assert any(f.rule == "f64-promotion" for f in findings)
        assert audit_jaxpr(closed, AuditSpec(allow_f64=True), where="t") == []

    def test_non_donated_carry_detected(self):
        big = jnp.ones((64, 64), jnp.float32)   # 16 KiB

        @jax.jit
        def undonated_step(state):
            return state * 2

        closed = jax.make_jaxpr(lambda: undonated_step(big))()
        findings = audit_jaxpr(
            closed, AuditSpec(expect_donation=("undonated_step",)),
            where="t")
        assert any(f.rule == "non-donated-carry" for f in findings)

        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def donated_step(state):
            return state * 2

        closed = jax.make_jaxpr(lambda: donated_step(jnp.copy(big)))()
        assert audit_jaxpr(
            closed, AuditSpec(expect_donation=("donated_step",)),
            where="t") == []

    def test_missing_expected_jit_detected(self):
        closed = jax.make_jaxpr(lambda: jnp.ones(3) * 2)()
        findings = audit_jaxpr(closed, AuditSpec(expect_donation=("epoch",)),
                               where="t")
        assert any(f.rule == "non-donated-carry" and "no such pjit" in f.message
                   for f in findings)

    def test_collective_axis_mismatch_detected(self):
        from jax.sharding import PartitionSpec as P

        from repro.core.compat import make_mesh, shard_map

        mesh = make_mesh((1,), ("rogue",))

        def summed(x):
            return shard_map(lambda b: jax.lax.psum(b, "rogue"), mesh=mesh,
                             in_specs=P("rogue"), out_specs=P())(x)

        closed = jax.make_jaxpr(lambda: summed(jnp.ones(4)))()
        findings = audit_jaxpr(
            closed, AuditSpec(declared_axes=frozenset({"data"})), where="t")
        assert any(f.rule == "collective-axis" and "rogue" in f.message
                   for f in findings)
        # with the axis declared, the same jaxpr passes
        assert audit_jaxpr(
            closed, AuditSpec(declared_axes=frozenset({"rogue"})),
            where="t") == []

    def test_registered_manifest_is_green(self):
        """The CI leg's exact contract: every auditable hot path clean."""
        findings, audited, _ = run_audit()
        assert findings == [], findings
        assert len(audited) >= 6
        assert len(hot_paths()) >= 8


class TestRetraceSentinel:
    def test_cold_jit_trips(self):
        @jax.jit
        def f(x):
            return x + 1

        with pytest.raises(RetraceError, match="observed"):
            with assert_no_retrace("cold call"):
                f(jnp.ones(7))

    def test_warm_jit_passes_and_watch_counts(self):
        @jax.jit
        def f(x):
            return x + 1

        with watch_compiles() as w:
            f(jnp.ones(8))
        assert w.compiles >= 1
        # input built outside the guard: only f's dispatch is under watch
        x2 = jnp.ones(8) * 3
        with assert_no_retrace("warmed call"):
            f(x2)

    def test_allowance(self):
        @jax.jit
        def f(x):
            return x * 2

        x = jnp.ones(9)
        with assert_no_retrace("declared one-off", allow=2):
            f(x)

    def test_shape_drift_is_caught(self):
        @jax.jit
        def f(x):
            return x.sum()

        f(jnp.ones(4))
        with pytest.raises(RetraceError):
            with assert_no_retrace("drifted shape"):
                f(jnp.ones(5))

    def test_stacked_segments_compile_once_emulated(self):
        """Fast twin of the mesh determinism check: after the first rung
        segment, later segments (new start_epoch / active / offsets) ride
        the SAME compiled epoch — the PR-3 claim as an assert."""
        from repro.core.optimizer import sgd_trial_round
        from repro.core.runner import DistributedRunner

        k, d = 4, 8
        runner = DistributedRunner(num_shards=4)
        grad = lambda vec, w, hyper: (vec[1:] @ w - vec[0]) * vec[1:]
        step = sgd_trial_round(grad, local_batch_size=4)
        hyper = {"lr": jnp.full((k,), 0.1, jnp.float32),
                 "decay": jnp.ones((k,), jnp.float32),
                 "l1": jnp.zeros((k,), jnp.float32)}
        rng = np.random.default_rng(0)
        win = jnp.asarray(rng.normal(size=(64, d + 1)).astype(np.float32))
        stream = iter(lambda: {"data": win}, None)
        trials = jnp.zeros((k, d), jnp.float32)
        act2 = jnp.asarray([True, False, True, True])
        offs = jnp.asarray([0, 0, 4, 0], jnp.int32)

        warm = runner.run_stacked_epochs(stream, trials, hyper, step, 1,
                                         chunks_per_epoch=4)
        with assert_no_retrace("rung segments after the first"):
            seg2 = runner.run_stacked_epochs(
                stream, warm, hyper, step, 2, start_epoch=1, active=act2,
                chunks_per_epoch=4)
            runner.run_stacked_epochs(
                stream, seg2, hyper, step, 3, start_epoch=2, active=act2,
                round_offsets=offs, chunks_per_epoch=4)
