"""AST lint engine rule tests (analysis/lint.py, MUR001-006).

Each rule class gets a positive fixture (the seeded violation must be
found) and a negative fixture (the legal near-miss must stay clean) — the
ISSUE-1 acceptance contract.  Fixtures are written to tmp_path so
``lint_file`` runs the real file path end to end.
"""

import textwrap

import pytest

from murmura_tpu.analysis.lint import lint_file


def lint_src(tmp_path, src):
    f = tmp_path / "fixture.py"
    f.write_text(textwrap.dedent(src))
    return [fi.rule for fi in lint_file(f)]


class TestMUR001TracedBranch:
    def test_if_on_traced_value(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                if x > 0:
                    return x
                return -x
        """)
        assert rules == ["MUR001"]

    def test_while_on_traced_value(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                while x.sum() > 0:
                    x = x - 1
                return x
        """)
        assert "MUR001" in rules

    def test_for_over_traced_value(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(xs):
                total = 0
                for x in xs:
                    total = total + x
                return total
        """)
        assert "MUR001" in rules

    def test_branch_on_shape_is_clean(self, tmp_path):
        # .shape/.dtype/.ndim reads are static even on tracers.
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                if x.shape[0] > 4:
                    return x * 2
                return x
        """)
        assert rules == []

    def test_branch_on_static_loop_index_is_clean(self, tmp_path):
        # Iterating a static range must not taint the loop variable
        # (the krum.py candidate-assembly pattern).
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                out = x
                for a in range(4):
                    if a == 0:
                        out = out + a
                return out
        """)
        assert rules == []

    def test_branch_on_len_is_clean(self, tmp_path):
        # len(tracer) is a static Python int under jit, same as .shape[0]
        # (the documented taint-breaker contract).
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                if len(x) > 4:
                    return float(len(x)) + x
                return x
        """)
        assert rules == []

    def test_is_none_comparison_is_clean(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x, y=None):
                if y is None:
                    return x
                return x + y
        """)
        assert rules == []


class TestMUR002TracedAssert:
    def test_assert_on_traced_value(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                assert x.sum() > 0
                return x
        """)
        assert rules == ["MUR002"]

    def test_assert_on_static_is_clean(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                assert x.ndim == 2
                return x
        """)
        assert rules == []


class TestMUR003HostSync:
    @pytest.mark.parametrize("expr", [
        "x.item()", "x.tolist()", "float(x)", "int(x)", "np.asarray(x)",
    ])
    def test_host_sync_calls(self, tmp_path, expr):
        rules = lint_src(tmp_path, f"""
            import jax
            import numpy as np

            @jax.jit
            def f(x):
                v = {expr}
                return v
        """)
        assert rules == ["MUR003"]

    def test_print_of_traced_value(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                print(x)
                return x
        """)
        assert rules == ["MUR003"]

    def test_protocol_traced_names_are_scanned(self, tmp_path):
        # The AggregatorDef contract: `aggregate` compiles into the round
        # step even with no jit decorator in sight.
        rules = lint_src(tmp_path, """
            def aggregate(own, bcast, adj, round_idx, state, ctx):
                return own, state, {"n": float(own.sum())}
        """)
        assert rules == ["MUR003"]

    def test_float_of_shape_is_clean(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                scale = float(x.shape[0])
                return x / scale
        """)
        assert rules == []

    def test_print_of_constant_is_clean(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                print("tracing f")
                return x
        """)
        assert rules == []


class TestMUR004RecompileHazard:
    def test_jit_inside_loop(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            def run(xs):
                out = []
                for x in xs:
                    out.append(jax.jit(lambda v: v * 2)(x))
                return out
        """)
        assert "MUR004" in rules

    def test_traced_range_bound(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x, n):
                for _ in range(n):
                    x = x * 2
                return x
        """)
        assert "MUR004" in rules

    def test_static_argname_range_bound_is_clean(self, tmp_path):
        # n is declared static in the decorator: range(n) specializes per
        # value by design (the pallas_sketch pattern).
        rules = lint_src(tmp_path, """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("n",))
            def f(x, n):
                for _ in range(n):
                    x = x * 2
                return x
        """)
        assert rules == []

    def test_static_argnums_branch_is_clean(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit(static_argnums=(1,))
            def f(x, mode):
                if mode > 1:
                    return x * 2
                return x
        """)
        assert rules == []

    def test_jit_outside_loop_is_clean(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            def run(xs):
                g = jax.jit(lambda v: v * 2)
                return [g(x) for x in xs]
        """)
        assert rules == []


class TestMUR005ImportTimeAlloc:
    def test_module_scope_jnp_call(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax.numpy as jnp

            TABLE = jnp.zeros((128,), dtype=jnp.float32)
        """)
        assert rules == ["MUR005"]

    def test_module_scope_devices_call(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            N_DEV = jax.device_count()
        """)
        assert rules == ["MUR005"]

    def test_alloc_inside_function_is_clean(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax.numpy as jnp

            def table():
                return jnp.zeros((128,), dtype=jnp.float32)
        """)
        assert rules == []

    def test_kwonly_default_flagged(self, tmp_path):
        # Keyword-only defaults evaluate at import time just like
        # positional ones.
        rules = lint_src(tmp_path, """
            import jax

            def f(x, *, key=jax.random.PRNGKey(0)):
                return x
        """)
        assert rules == ["MUR005"]

    def test_numpy_module_scope_is_clean(self, tmp_path):
        # Host-side numpy at import time does not touch the XLA backend.
        rules = lint_src(tmp_path, """
            import numpy as np

            TABLE = np.zeros((128,), dtype=np.float32)
        """)
        assert rules == []


class TestMUR006DtypePromotion:
    def test_dtypeless_ctor_with_traced_operand(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return x * jnp.ones(x.shape)
        """)
        assert rules == ["MUR006"]

    def test_explicit_dtype_is_clean(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                return x * jnp.ones(x.shape, dtype=x.dtype)
        """)
        assert rules == []

    def test_ctor_without_traced_operand_is_clean(self, tmp_path):
        # A dtype-less constructor alone is fine — the hazard is the
        # promotion against traced (possibly bf16) state.
        rules = lint_src(tmp_path, """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                mask = 1.0 - jnp.eye(4)
                return x.sum() + mask.sum()
        """)
        assert rules == []


class TestSuppression:
    def test_ignore_specific_rule(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                v = x.item()  # murmura: ignore[MUR003]
                return v
        """)
        assert rules == []

    def test_ignore_bare(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                v = float(x)  # murmura: ignore
                return v
        """)
        assert rules == []

    def test_ignore_other_rule_does_not_suppress(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                v = float(x)  # murmura: ignore[MUR001]
                return v
        """)
        assert rules == ["MUR003"]

    def test_traced_marker_opts_function_in(self, tmp_path):
        rules = lint_src(tmp_path, """
            def helper(x):  # murmura: traced
                return float(x)
        """)
        assert rules == ["MUR003"]


class TestScopeDiscovery:
    def test_function_passed_to_scan_is_traced(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            def body(carry, x):
                if x > 0:
                    carry = carry + x
                return carry, x

            def run(xs):
                return jax.lax.scan(body, 0.0, xs)
        """)
        assert rules == ["MUR001"]

    def test_nested_def_inherits_closure_taint(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            def build(model):
                def train_round(params, data):
                    def inner():
                        return float(params)
                    return inner()
                return jax.jit(train_round)
        """)
        assert rules == ["MUR003"]

    def test_lambda_passed_to_jit_is_traced(self, tmp_path):
        # The network.py `jax.jit(lambda tree: ...)` pattern: a lambda in a
        # tracing call's function slot is a traced scope too.
        rules = lint_src(tmp_path, """
            import jax

            g = jax.jit(lambda x: float(x))
        """)
        assert rules == ["MUR003"]

    def test_jit_lambda_inside_traced_fn_not_duplicated(self, tmp_path):
        # Scanned both by the enclosing taint pass and by module-level
        # lambda collection — the finding must appear exactly once.
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                g = jax.jit(lambda v: float(v))
                return g(x)
        """)
        assert rules == ["MUR003"]

    def test_plain_function_is_not_traced(self, tmp_path):
        # No decorator, no protocol name, never passed to a tracing call:
        # host code may branch/print/convert freely.
        rules = lint_src(tmp_path, """
            def orchestrate(history):
                if history:
                    print(history[-1])
                return float(len(history))
        """)
        assert rules == []

    def test_syntax_error_reports_mur000(self, tmp_path):
        f = tmp_path / "bad.py"
        f.write_text("def broken(:\n")
        findings = lint_file(f)
        assert [fi.rule for fi in findings] == ["MUR000"]
        assert findings[0].name == "syntax-error"  # not "[unknown]"

    def test_unreadable_file_reports_mur000(self, tmp_path):
        # A non-UTF8 file must be a per-file finding, not a crash that
        # aborts the whole `murmura check` run.
        f = tmp_path / "latin1.py"
        f.write_bytes(b"# caf\xe9\nx = 1\n")
        findings = lint_file(f)
        assert [fi.rule for fi in findings] == ["MUR000"]
        assert "unreadable" in findings[0].message


class TestWithAsTaint:
    def test_with_as_traced_target_flagged(self, tmp_path):
        rules = lint_src(tmp_path, """
            import jax

            @jax.jit
            def f(x, ctx):
                with ctx.scope(x) as y:
                    if y > 0:
                        return y
                return x
        """)
        assert "MUR001" in rules

    def test_with_as_rebind_breaks_taint(self, tmp_path):
        # `as` rebinds the name: a previously traced name bound to a
        # static context value must not keep its old taint.
        rules = lint_src(tmp_path, """
            import functools

            import jax

            @functools.partial(jax.jit, static_argnames=("opts",))
            def f(x, opts):
                y = x
                with opts.scope() as y:
                    if y > 0:
                        return x
                return x
        """)
        assert rules == []
