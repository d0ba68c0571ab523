"""reprolint fixture suite: every rule exercised on good and bad
in-memory snippets, suppression-comment semantics, the trace-scope
closure (nested jit scopes, aliases, the timer allowlist), the CLI
surface, and the self-check that the repo itself lints clean.

These tests are pure stdlib + the in-tree linter — no JAX import — so
they run first and fast.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

from tools.reprolint import (ALL_RULES, lint_paths, lint_source,
                             lint_sources)

REPO = Path(__file__).resolve().parents[1]


def rules_of(findings):
    return [f.rule for f in findings]


def lint(src, path="snippet.py", only=None):
    return lint_source(textwrap.dedent(src), path=path, only=only)


# ----------------------------------------------------------------------
# RPL101/RPL102 — single decision point
# ----------------------------------------------------------------------
class TestDispatchRules:
    def test_flags_config_attribute_read(self):
        out = lint("""
            def pick(tc):
                if tc.fused_outer:
                    return "fused"
            """, path="src/repro/core/bpt_trainer.py", only=["RPL101"])
        assert rules_of(out) == ["RPL101"]
        assert "fused_outer" in out[0].message

    def test_flags_getattr_spelling(self):
        out = lint("""
            def pick(cfg):
                return getattr(cfg, "mesh_name")
            """, path="src/repro/core/x.py", only=["RPL101"])
        assert rules_of(out) == ["RPL101"]

    def test_dotted_receiver_terminal_matches(self):
        out = lint("""
            class T:
                def go(self):
                    return self.tc.device_outer
            """, path="src/repro/core/x.py", only=["RPL101"])
        assert rules_of(out) == ["RPL101"]

    def test_engine_module_is_allowed(self):
        out = lint("""
            def resolve_engine(tc):
                return tc.fused_outer, tc.mesh_name
            """, path="src/repro/core/engine.py", only=["RPL101"])
        assert out == []

    def test_non_config_receiver_is_clean(self):
        out = lint("""
            def run(args, plan):
                return args.batching, plan.batching
            """, path="src/repro/launch/serve.py", only=["RPL102"])
        assert out == []

    def test_constructor_keyword_is_clean(self):
        out = lint("""
            def mk():
                return TrainConfig(fused_outer=True, mesh_name="pod")
            """, path="src/repro/core/x.py", only=["RPL101"])
        assert out == []

    def test_serve_fields_flag_outside_serving_engine(self):
        out = lint("""
            def pick(sc):
                return sc.batching == "continuous" and sc.timing
            """, path="src/repro/serving/cache.py", only=["RPL102"])
        assert sorted(rules_of(out)) == ["RPL102", "RPL102"]


# ----------------------------------------------------------------------
# RPL201/RPL202 — trace hygiene
# ----------------------------------------------------------------------
class TestTraceRules:
    def test_host_sync_in_jitted_function(self):
        out = lint("""
            import jax

            @jax.jit
            def step(x):
                jax.block_until_ready(x)
                return x
            """, only=["RPL201"])
        assert rules_of(out) == ["RPL201"]
        assert "block_until_ready" in out[0].message

    def test_callsite_wrapping_and_transitive_reach(self):
        # helper() is only reachable through step(), which is jitted at
        # a call site — the closure must follow both hops
        out = lint("""
            import jax, numpy as np

            def helper(x):
                return np.asarray(x)

            def step(x):
                return helper(x)

            run = jax.jit(step)
            """, only=["RPL201"])
        assert rules_of(out) == ["RPL201"]
        assert "np.asarray" in out[0].message

    def test_nested_jit_scope_inner_def(self):
        # a def nested inside a traced def runs at trace time too
        out = lint("""
            import jax, time

            @jax.jit
            def outer(x):
                def inner(y):
                    return y * time.perf_counter()
                return inner(x)
            """, only=["RPL202"])
        assert rules_of(out) == ["RPL202"]
        assert "inner" in out[0].message or "outer" in out[0].message

    def test_partial_decorator_and_scan_body(self):
        out = lint("""
            import jax, random
            from functools import partial

            def body(carry, x):
                return carry + random.random(), x

            def roll(xs):
                return jax.lax.scan(body, 0.0, xs)
            """, only=["RPL202"])
        assert rules_of(out) == ["RPL202"]

    def test_untraced_function_is_clean(self):
        out = lint("""
            import time

            def bench(f):
                t0 = time.perf_counter()
                f()
                return time.perf_counter() - t0
            """, only=["RPL201", "RPL202"])
        assert out == []

    def test_host_span_in_trace_is_flagged(self):
        out = lint("""
            import jax
            from repro.sanitize import span

            def step(x):
                with span("dispatch"):
                    return x + 1

            def round_fn(x):
                with jax.profiler.TraceAnnotation("bpt.round"):
                    return x * 2

            run = jax.jit(step)
            run2 = jax.jit(round_fn)
            """, only=["RPL202"])
        assert rules_of(out) == ["RPL202", "RPL202"]
        assert "host span" in out[0].message

    def test_host_span_around_a_dispatch_is_clean(self):
        out = lint("""
            import jax
            from repro.sanitize import span

            step = jax.jit(lambda x: x + 1)

            def run_round(x):
                with span("dispatch"):
                    return step(x)
            """, only=["RPL202"])
        assert out == []

    def test_jax_random_is_not_nondet(self):
        out = lint("""
            import jax

            @jax.jit
            def draw(key):
                return jax.random.normal(key, (4,))
            """, only=["RPL202"])
        assert out == []

    def test_timer_allowlist_exempts_measured_timer(self):
        out = lint("""
            import time, jax

            class MeasuredTimer:
                def call(self, f, x):
                    t0 = time.perf_counter()
                    y = jax.block_until_ready(f(x))
                    return y, time.perf_counter() - t0

            probe = jax.jit(MeasuredTimer.call)
            """, only=["RPL201", "RPL202"])
        assert out == []

    def test_item_pull_flags_but_methodful_item_does_not(self):
        out = lint("""
            import jax

            @jax.jit
            def bad(x):
                return float(x.item())

            @jax.jit
            def fine(d):
                return d.item(0)
            """, only=["RPL201"])
        assert rules_of(out) == ["RPL201"]
        assert out[0].line == 6


# ----------------------------------------------------------------------
# RPL301/RPL302/RPL303 — kernel contracts
# ----------------------------------------------------------------------
KERNEL_OK = """
import jax
from jax.experimental import pallas as pl

@jax.custom_vjp
def dense_pallas(x, w):
    return pl.pallas_call(lambda r: r)(x, w)

def _fwd(x, w):
    return dense_pallas(x, w), (x, w)

def _bwd(res, g):
    return g, g

dense_pallas.defvjp(_fwd, _bwd)
"""

KERNEL_NO_VJP = """
from jax.experimental import pallas as pl

def dense_pallas(x, w):
    return pl.pallas_call(lambda r: r)(x, w)
"""

OPS_ROUTING = """
def dense(x, w, impl="auto"):
    if impl == "pallas":
        try:
            from . import dense_kernel
            return dense_kernel.dense_pallas(x, w)
        except Exception as e:
            _fallback("dense", str(e), explicit=(impl == "pallas"))
    return _dense_ref(x, w)
"""


class TestKernelRules:
    def test_missing_vjp_flags(self):
        out = lint_sources(
            {"src/repro/kernels/dense_kernel.py": KERNEL_NO_VJP},
            only=["RPL301"])
        assert rules_of(out) == ["RPL301"]
        assert "dense_pallas" in out[0].message

    def test_paired_vjp_is_clean(self):
        out = lint_sources(
            {"src/repro/kernels/dense_kernel.py": KERNEL_OK},
            only=["RPL301"])
        assert out == []

    def test_rule_skips_non_kernel_modules(self):
        out = lint_sources({"src/repro/models/cnn.py": KERNEL_NO_VJP},
                           only=["RPL301"])
        assert out == []

    def test_unrouted_kernel_flags(self):
        out = lint_sources({
            "src/repro/kernels/dense_kernel.py": KERNEL_OK,
            "src/repro/kernels/ops.py": "def dense(x, w):\n    return x\n",
        }, only=["RPL303"])
        assert rules_of(out) == ["RPL303"]

    def test_routed_kernel_is_clean(self):
        out = lint_sources({
            "src/repro/kernels/dense_kernel.py": KERNEL_OK,
            "src/repro/kernels/ops.py": OPS_ROUTING,
        }, only=["RPL303"])
        assert out == []

    def test_silent_fallback_flags(self):
        out = lint("""
            def dense(x, w, impl="auto"):
                if impl == "pallas":
                    y = _try_kernel(x, w)
                return _dense_ref(x, w)
            """, path="src/repro/kernels/ops.py", only=["RPL302"])
        assert rules_of(out) == ["RPL302"]

    def test_fallback_contract_is_clean(self):
        out = lint_sources({"src/repro/kernels/ops.py": OPS_ROUTING},
                           only=["RPL302"])
        assert out == []

    def test_suite_ending_in_return_is_clean(self):
        out = lint("""
            def rmsnorm(x, s, impl="auto"):
                if impl == "pallas":
                    return _rmsnorm_pallas(x, s)
                return _rmsnorm_ref(x, s)
            """, path="src/repro/kernels/ops.py", only=["RPL302"])
        assert out == []


# ----------------------------------------------------------------------
# RPL401/RPL402/RPL403 — deprecation bans
# ----------------------------------------------------------------------
class TestDeprecationRules:
    def test_greedy_generate_import_and_call_flag(self):
        out = lint("""
            from repro.launch.serve import greedy_generate

            def go(params, cfg, prompts):
                return greedy_generate(params, cfg, prompts, 16, 4)
            """, path="examples/demo.py", only=["RPL401"])
        assert rules_of(out) == ["RPL401", "RPL401"]

    def test_shim_module_is_allowed(self):
        out = lint("def greedy_generate(*a):\n    return None\n",
                   path="src/repro/launch/serve.py", only=["RPL401"])
        assert out == []

    def test_legacy_init_cache_order_flags(self):
        out = lint("""
            def warm(cfg):
                return init_cache(cfg, 2, 16)
            """, only=["RPL402"])
        assert rules_of(out) == ["RPL402"]

    def test_legacy_getattr_spelling_flags(self):
        out = lint("""
            def warm(lm, cfg):
                return getattr(lm, "init_cache")(cfg, 2, 16)
            """, only=["RPL402"])
        assert rules_of(out) == ["RPL402"]

    def test_new_order_is_clean(self):
        out = lint("""
            def warm(cfg):
                return init_cache(2, 16, cfg=cfg)
            """, only=["RPL402"])
        assert out == []

    def test_pythonpath_runline_flags_with_line_number(self):
        out = lint('''
            """Driver.

                PYTHONPATH=src python -m repro.launch.x --go
            """
            X = 1
            ''', only=["RPL403"])
        assert rules_of(out) == ["RPL403"]
        assert out[0].line == 4

    def test_prose_mention_is_clean(self):
        out = lint('''
            """Driver.

                python -m repro.launch.x --go

            (bare checkouts can prefix ``PYTHONPATH=src``.)
            """
            ''', only=["RPL403"])
        assert out == []


# ----------------------------------------------------------------------
# RPL501 — donation safety
# ----------------------------------------------------------------------
class TestDonationRule:
    def test_reuse_after_donation_flags(self):
        out = lint("""
            import jax

            def train(step, params, opt, batches):
                run = jax.jit(step, donate_argnums=(0,))
                out = run(params, opt)
                return params["w"]
            """, only=["RPL501"])
        assert rules_of(out) == ["RPL501"]
        assert "`params`" in out[0].message

    def test_rebind_from_result_is_clean(self):
        out = lint("""
            import jax

            def train(step, params, opt, batches):
                run = jax.jit(step, donate_argnums=(0, 1))
                for b in batches:
                    params, opt = run(params, opt)
                return params
            """, only=["RPL501"])
        assert out == []

    def test_non_donated_arg_is_clean(self):
        out = lint("""
            import jax

            def train(step, params, opt):
                run = jax.jit(step, donate_argnums=(0,))
                new_params = run(params, opt)
                return opt
            """, only=["RPL501"])
        assert out == []

    def test_donation_does_not_leak_across_functions(self):
        out = lint("""
            import jax

            def a(step, params):
                run = jax.jit(step, donate_argnums=(0,))
                return run(params)

            def b(params):
                return params
            """, only=["RPL501"])
        assert out == []


# ----------------------------------------------------------------------
# suppressions, parse errors, engine surface
# ----------------------------------------------------------------------
class TestEngineBehaviour:
    def test_inline_suppression_by_id_and_name(self):
        src = """
            def pick(tc):
                a = tc.fused_outer  # reprolint: disable=RPL101
                b = tc.device_outer  # reprolint: disable=dispatch-train
                c = tc.mesh_name
            """
        out = lint(src, path="src/repro/core/x.py", only=["RPL101"])
        assert len(out) == 1 and out[0].line == 5

    def test_suppress_all_token(self):
        out = lint(
            "def f(tc):\n"
            "    return tc.fused_outer  # reprolint: disable=all\n",
            path="src/repro/core/x.py", only=["RPL101"])
        assert out == []

    def test_suppression_is_line_scoped(self):
        out = lint(
            "# reprolint: disable=RPL101\n"
            "def f(tc):\n"
            "    return tc.fused_outer\n",
            path="src/repro/core/x.py", only=["RPL101"])
        assert len(out) == 1

    def test_parse_error_reports_rpl000_unsuppressable(self):
        out = lint_source(
            "def broken(:  # reprolint: disable=all\n")
        assert rules_of(out) == ["RPL000"]

    def test_unknown_rule_selection_raises(self):
        try:
            lint_source("x = 1\n", only=["RPL999"])
        except ValueError as e:
            assert "RPL999" in str(e)
        else:
            raise AssertionError("expected ValueError")

    def test_rule_ids_unique_and_named(self):
        ids = [r.id for r in ALL_RULES]
        names = [r.name for r in ALL_RULES]
        assert len(set(ids)) == len(ids)
        assert len(set(names)) == len(names)
        assert all(r.description for r in ALL_RULES)

    def test_findings_sorted_and_formatted(self):
        out = lint("""
            def pick(tc):
                b = tc.device_outer
                a = tc.fused_outer
            """, path="src/repro/core/x.py", only=["RPL101"])
        assert [f.line for f in out] == sorted(f.line for f in out)
        assert out[0].format().startswith("src/repro/core/x.py:3:")


# ----------------------------------------------------------------------
# RPL601–RPL605 — shardcheck: mesh/collective static analysis
# ----------------------------------------------------------------------
class TestShardcheckRules:
    def test_axis_unbound_by_enclosing_mesh_flags(self):
        """psum("model") inside a shard_map over a 1-D `nodes` mesh: the
        axis exists in the repo vocabulary but is NOT bound here."""
        out = lint("""
            import jax
            from jax.experimental.shard_map import shard_map
            from repro.launch.mesh import make_nodes_mesh
            mesh = make_nodes_mesh(4)
            def body(x):
                return jax.lax.psum(x, "model")
            sm = shard_map(body, mesh=mesh, in_specs=None, out_specs=None)
            """, path="src/repro/core/x.py", only=["RPL601"])
        assert rules_of(out) == ["RPL601"]
        assert "'model'" in out[0].message and "nodes" in out[0].message

    def test_axis_bound_by_hybrid_mesh_is_clean(self):
        out = lint("""
            import jax
            from jax.experimental.shard_map import shard_map
            from repro.launch.mesh import make_hybrid_mesh
            mesh = make_hybrid_mesh(4, 2)
            def body(x):
                i = jax.lax.axis_index("nodes")
                return jax.lax.psum(x, "model") + i
            sm = shard_map(body, mesh=mesh, in_specs=None, out_specs=None)
            """, path="src/repro/core/x.py", only=["RPL601"])
        assert out == []

    def test_axis_outside_vocabulary_flags_anywhere(self):
        out = lint("""
            import jax
            def f(x):
                return jax.lax.all_gather(x, "banana")
            """, path="src/repro/models/y.py", only=["RPL601"])
        assert rules_of(out) == ["RPL601"]
        assert "banana" in out[0].message

    def test_named_mesh_resolves_through_registry(self):
        """make_mesh("hyb") binds (nodes, model) cross-FILE through the
        MESHES dict in the project's launch/mesh.py."""
        out = lint_sources({
            "launch/mesh.py": textwrap.dedent("""
                MESHES = {
                    "hyb": ((4, 2), ("nodes", "model")),
                    "flat": ((8,), ("data",)),
                }
                """),
            "core/x.py": textwrap.dedent("""
                import jax
                from jax.experimental.shard_map import shard_map
                from repro.launch.mesh import make_mesh
                mesh = make_mesh("hyb")
                def body(x):
                    return jax.lax.psum(x, "data")
                sm = shard_map(body, mesh=mesh, in_specs=None,
                               out_specs=None)
                """),
        }, only=["RPL601"])
        assert rules_of(out) == ["RPL601"]
        assert "'data'" in out[0].message and "nodes" in out[0].message

    def test_unresolvable_axis_name_is_skipped(self):
        """Axis names flowing through parameters (planner idiom
        ``axis = plan.axis``) are skipped, not guessed."""
        out = lint("""
            import jax
            def combine(loss, axis):
                return jax.lax.psum(loss, axis)
            """, path="src/repro/core/x.py", only=["RPL601"])
        assert out == []

    def test_axis_default_parameter_resolves(self):
        out = lint("""
            import jax
            def f(x, axis_name="bogus"):
                return jax.lax.psum(x, axis_name)
            """, path="src/repro/core/x.py", only=["RPL601"])
        assert rules_of(out) == ["RPL601"]

    def test_eq7_merge_over_model_flags(self):
        """THE fixture of the PR: a mis-axed Eq. 7 merge — psum over
        `model` inside the GWU scope merges the wrong groups."""
        out = lint("""
            import jax
            def _sharded_merge_fn(mesh):
                def body(stack, w):
                    return jax.lax.psum(stack * w, "model")
                return body
            """, path="src/repro/core/gwu.py", only=["RPL602"])
        assert rules_of(out) == ["RPL602"]
        assert "'model'" in out[0].message and "nodes" in out[0].message

    def test_eq7_merge_over_nodes_is_clean(self):
        out = lint("""
            import jax
            def sgwu_merge(stack, w):
                i = jax.lax.axis_index("model")   # index read: not a merge
                return jax.lax.psum(stack * w, "nodes")
            """, path="src/repro/core/x.py", only=["RPL602"])
        assert out == []

    def test_planner_model_psum_is_out_of_eq7_scope(self):
        out = lint("""
            import jax
            def grad_combine_over_model(loss):
                return jax.lax.psum(loss, "model")
            """, path="src/repro/core/planner.py", only=["RPL602"])
        assert out == []

    def test_orphan_spec_outside_owner_flags(self):
        out = lint("""
            from jax.sharding import PartitionSpec as P
            SPEC = P("nodes")
            """, path="src/repro/core/x.py", only=["RPL603"])
        assert rules_of(out) == ["RPL603"]
        assert "planner" in out[0].message

    def test_spec_in_owner_module_is_clean(self):
        out = lint("""
            from jax.sharding import PartitionSpec as P
            SPEC = P("nodes")
            """, path="src/repro/core/planner.py", only=["RPL603"])
        assert out == []

    def test_spec_shipped_with_mesh_op_is_clean(self):
        out = lint("""
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            def place(x, mesh):
                return jax.device_put(x, NamedSharding(mesh, P("nodes")))
            """, path="src/repro/core/x.py", only=["RPL603"])
        assert out == []

    def test_spec_shipped_via_local_name_is_clean(self):
        """bpt_trainer idiom: batch_spec = P("nodes") referenced by the
        shard_map in_specs ships the spec."""
        out = lint("""
            from jax.experimental.shard_map import shard_map
            from jax.sharding import PartitionSpec as P
            def build(body, mesh):
                batch_spec = P("nodes")
                return shard_map(body, mesh=mesh,
                                 in_specs=(batch_spec,), out_specs=P())
            """, path="src/repro/core/x.py", only=["RPL603"])
        assert out == []

    def test_spec_axis_outside_vocabulary_flags(self):
        out = lint("""
            from jax.sharding import NamedSharding, PartitionSpec as P
            def place(mesh):
                return NamedSharding(mesh, P("bogus"))
            """, path="src/repro/launch/sharding.py", only=["RPL603"])
        assert rules_of(out) == ["RPL603"]
        assert "bogus" in out[0].message

    def test_dynamic_spec_is_skipped(self):
        out = lint("""
            from jax.sharding import PartitionSpec as P
            def spec_for(axes):
                return P(*axes)
            EMPTY = P()
            """, path="src/repro/core/x.py", only=["RPL603"])
        assert out == []

    def test_unregistered_dataclass_in_traced_code_flags(self):
        out = lint("""
            import dataclasses, jax
            @dataclasses.dataclass
            class Cache:
                x: int
            @jax.jit
            def step(a):
                return Cache(a)
            """, path="src/repro/models/c.py", only=["RPL604"])
        assert rules_of(out) == ["RPL604"]
        assert "Cache" in out[0].message

    def test_registered_dataclass_is_clean(self):
        out = lint("""
            import dataclasses, jax
            @dataclasses.dataclass
            class Cache:
                x: int
            jax.tree_util.register_dataclass(Cache)
            @jax.jit
            def step(a):
                return Cache(a)
            """, path="src/repro/models/c.py", only=["RPL604"])
        assert out == []

    def test_untraced_dataclass_construction_is_clean(self):
        out = lint("""
            import dataclasses
            @dataclasses.dataclass
            class Report:
                x: int
            def summarize(a):
                return Report(a)
            """, path="src/repro/models/c.py", only=["RPL604"])
        assert out == []

    def test_pallas_in_shardmap_without_check_rep_flags(self):
        out = lint("""
            from jax.experimental.shard_map import shard_map
            from jax.experimental import pallas as pl
            def body(x):
                return pl.pallas_call(kern, out_shape=None)(x)
            sm = shard_map(body, mesh=m, in_specs=None, out_specs=None)
            """, path="src/repro/models/k.py", only=["RPL605"])
        assert rules_of(out) == ["RPL605"]
        assert "check_rep" in out[0].message

    def test_pallas_in_shardmap_with_check_rep_false_is_clean(self):
        out = lint("""
            from jax.experimental.shard_map import shard_map
            from jax.experimental import pallas as pl
            def body(x):
                return pl.pallas_call(kern, out_shape=None)(x)
            sm = shard_map(body, mesh=m, in_specs=None, out_specs=None,
                           check_rep=False)
            """, path="src/repro/models/k.py", only=["RPL605"])
        assert out == []

    def test_pallas_free_shardmap_needs_no_check_rep(self):
        out = lint("""
            from jax.experimental.shard_map import shard_map
            def body(x):
                return x + 1
            sm = shard_map(body, mesh=m, in_specs=None, out_specs=None)
            """, path="src/repro/models/k.py", only=["RPL605"])
        assert out == []

    def test_fixture_project_without_mesh_module_uses_default_axes(self):
        """In-memory projects with no launch/mesh.py fall back to the
        default axis vocabulary instead of crashing or flagging all."""
        out = lint_sources({"core/a.py": textwrap.dedent("""
            import jax
            def f(x):
                return jax.lax.psum(x, "nodes")
            """)}, only=["RPL601"])
        assert out == []

    def test_mesh_module_inside_fixture_project_wins(self):
        """A fixture project that carries its own launch/mesh.py defines
        the vocabulary — cross-FILE resolution inside lint_sources."""
        out = lint_sources({
            "launch/mesh.py": 'MESHES = {"m": ((2,), ("ring",))}\n',
            "core/a.py": textwrap.dedent("""
                import jax
                def f(x):
                    return jax.lax.psum(x, "nodes")
                """),
        }, only=["RPL601"])
        assert rules_of(out) == ["RPL601"]
        assert "ring" in out[0].message


# ----------------------------------------------------------------------
# the repo itself + the CLI
# ----------------------------------------------------------------------
class TestRepoIsClean:
    def test_repo_lints_clean(self):
        """The acceptance bar for the whole PR: the tree carries zero
        unsuppressed findings across every rule."""
        findings = lint_paths(
            [str(REPO / d) for d in ("src", "tests", "examples")
             if (REPO / d).exists()])
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_cli_json_and_exit_codes(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("def f(tc):\n    return tc.fused_outer\n")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.reprolint", str(bad),
             "--format", "json", "--json-report",
             str(tmp_path / "report.json")],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 1
        payload = json.loads(proc.stdout)
        assert payload["by_rule"] == {"RPL101": 1}
        report = json.loads((tmp_path / "report.json").read_text())
        assert report["findings"][0]["rule"] == "RPL101"

        good = tmp_path / "good.py"
        good.write_text("x = 1\n")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.reprolint", str(good)],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0
        assert "0 findings" in proc.stdout

    def test_cli_only_and_disable_flags(self, tmp_path):
        """--only narrows the rule set, --disable carves rules out of it,
        and the JSON report carries zero-inclusive per-rule counts for
        exactly the rules that RAN."""
        bad = tmp_path / "bad.py"
        # trips RPL101 (config read) AND RPL601 (bogus collective axis)
        bad.write_text(
            "import jax\n"
            "def f(tc, x):\n"
            "    if tc.fused_outer:\n"
            "        return jax.lax.psum(x, 'banana')\n")

        proc = subprocess.run(
            [sys.executable, "-m", "tools.reprolint", str(bad),
             "--only", "RPL101,RPL601", "--disable", "RPL101",
             "--format", "json"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 1
        payload = json.loads(proc.stdout)
        assert payload["by_rule"] == {"RPL601": 1}
        # per-rule counts: RPL601 ran and found; RPL101 was disabled so
        # it has NO entry (absent != zero)
        assert payload["rules"] == {
            "RPL601": {"name": "collective-axis-unbound", "findings": 1}}

        # symbolic names work too, and a disabled-to-clean run exits 0
        proc = subprocess.run(
            [sys.executable, "-m", "tools.reprolint", str(bad),
             "--disable", "dispatch-train,collective-axis-unbound",
             "--format", "json"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0
        payload = json.loads(proc.stdout)
        assert payload["findings"] == []
        assert "RPL101" not in payload["rules"]
        assert payload["rules"]["RPL605"]["findings"] == 0

        # unknown rule names are usage errors (exit 2), not silent no-ops
        proc = subprocess.run(
            [sys.executable, "-m", "tools.reprolint", str(bad),
             "--disable", "RPL999"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 2
        assert "RPL999" in proc.stderr
