"""Static-analysis subsystem (src/repro/analysis/).

The contract under test is *detection*: each gate must fire on a seeded
violation of its class (f64 leak, implicit-upcast dot, bf16 accumulator,
key arithmetic, host callback, CLIP scatter, OOB index map, VMEM blowout,
bare assert, key reuse, hardcoded interpret) and stay silent on the
idiomatic pattern right next to it — otherwise the CI `analysis` job passes
vacuously. Plus: baseline round-trip semantics, the CLI gate's exit codes,
and (behind BENCH_SMOKE=1) the streaming recompilation guard.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import baseline as B
from repro.analysis import jaxpr_audit as JA
from repro.analysis import kernel_check as KC
from repro.analysis import repo_lint as RL
from repro.analysis.__main__ import main as cli_main
from repro.core import graph as G
from repro.kernels.spec import BlockMeta, KernelSpec, grid_points

_SILENT = lambda *a, **k: None  # noqa: E731


def _audit(fn, *avals):
    return JA.audit_closed_jaxpr("fixture", jax.make_jaxpr(fn)(*avals))


def _rules(findings):
    return {f.rule for f in findings}


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


# ---------------------------------------------------------------- jaxpr audit

class TestJaxprAudit:
    def test_f64_leak_flagged(self):
        # the exact deployment bug: library code is traced under an
        # x64-enabled host process and a np.float64 scalar promotes the
        # whole chain to f64
        with jax.enable_x64(True):
            found = _audit(lambda x: x * np.float64(2.0), _f32(4))
        assert "wide-dtype" in _rules(found)

    def test_f32_scalar_clean(self):
        assert not _audit(lambda x: x * 2.0, _f32(4))

    def test_mixed_dot_flagged(self):
        dims = (((1,), (0,)), ((), ()))
        found = _audit(lambda a, b: jax.lax.dot_general(a, b, dims),
                       _bf16(4, 4), _f32(4, 4))
        assert "mixed-dot" in _rules(found)

    def test_bf16_dot_without_f32_accum_flagged(self):
        dims = (((1,), (0,)), ((), ()))
        found = _audit(lambda a, b: jax.lax.dot_general(a, b, dims),
                       _bf16(4, 4), _bf16(4, 4))
        assert "low-precision-accum" in _rules(found)

    def test_bf16_dot_with_f32_accum_clean(self):
        dims = (((1,), (0,)), ((), ()))
        found = _audit(
            lambda a, b: jax.lax.dot_general(
                a, b, dims, preferred_element_type=jnp.float32),
            _bf16(4, 4), _bf16(4, 4))
        assert not found

    def test_key_arithmetic_flagged(self):
        found = _audit(lambda d: G.dist_key(d) + 1, _f32(4))
        assert "key-taint" in _rules(found)

    def test_key_float_cast_flagged(self):
        found = _audit(lambda d: G.dist_key(d).astype(jnp.float32), _f32(4))
        assert "key-taint" in _rules(found)

    def test_key_taint_threads_through_pjit(self):
        # jnp.where arrives as a pjit sub-jaxpr; taint must survive the
        # call boundary or every real key path goes unaudited
        def f(d):
            k = G.dist_key(d)
            k = jnp.where(d > 0, k, jnp.uint32(0))
            return k * 2
        assert "key-taint" in _rules(_audit(f, _f32(4)))

    def test_legal_key_consumers_clean(self):
        # min-merge + decode + compare: the repo's actual key usage
        def f(d):
            k = jnp.minimum(G.dist_key(d), G.dist_key(d * 2))
            k = jnp.sort(k)
            return G.key_dist(k), k < jnp.uint32(7)
        assert not _audit(f, _f32(4))

    def test_scan_boundary_drops_taint(self):
        # documented limitation: taint is not threaded through scan carries
        # (real consumers re-taint at the inner bitcast) — lock the
        # documented behavior so a change here is a conscious one
        def f(d):
            k = G.dist_key(d)
            out, _ = jax.lax.scan(lambda c, _: (c + 1, ()), k,
                                  None, length=3)
            return out
        assert not _audit(f, _f32(4))

    def test_host_callback_flagged(self):
        def f(x):
            return jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct((4,), jnp.float32), x)
        assert "host-callback" in _rules(_audit(f, _f32(4)))

    def test_scatter_clip_flagged_drop_clean(self):
        idx = jnp.array([1, 2])
        clip = _audit(lambda x, v: x.at[idx].set(v, mode="clip"),
                      _f32(8), _f32(2))
        assert "scatter-clip" in _rules(clip)
        drop = _audit(lambda x, v: x.at[idx].set(v, mode="drop"),
                      _f32(8), _f32(2))
        assert not drop

    def test_search_entries_clean(self):
        # a cheap slice of the real registry (the full sweep is the CI
        # analysis job): every search entry must audit clean
        found = JA.run(["search"], log=_SILENT)
        assert not found, [str(f) for f in found]


# --------------------------------------------------------------- kernel check

def _spec(name="fixture", grid=(4,), array=(64, 8), block=(16, 8),
          index_map=lambda i: (i, 0), dtype=jnp.float32,
          vmem_limit=16 * 1024 * 1024, low_precision_inputs=(),
          trace=None):
    if trace is None:
        trace = lambda: jax.make_jaxpr(lambda x: x + 1)(  # noqa: E731
            jax.ShapeDtypeStruct(array, dtype))
    blk = lambda n: BlockMeta(n, array, block, dtype, index_map)  # noqa: E731
    return KernelSpec(name=name, grid=grid, inputs=(blk("a"),),
                      outputs=(blk("o"),), trace=trace,
                      low_precision_inputs=low_precision_inputs,
                      vmem_limit_bytes=vmem_limit)


class TestKernelCheck:
    def test_in_bounds_spec_clean(self):
        assert not KC.check_spec(_spec())

    def test_oob_index_map_flagged(self):
        # off-by-one block index: the last grid step reads tile [80, 96)
        # of a 64-row array — silent garbage on TPU (Mosaic clamps)
        found = KC.check_spec(_spec(index_map=lambda i: (i + 1, 0)))
        assert "oob-index-map" in _rules(found)

    def test_block_rank_mismatch_flagged(self):
        found = KC.check_spec(_spec(block=(16,), index_map=lambda i: (i,)))
        assert "oob-index-map" in _rules(found)

    def test_block_exceeding_array_flagged(self):
        found = KC.check_spec(_spec(block=(128, 8)))
        assert "oob-index-map" in _rules(found)

    def test_vmem_budget_flagged(self):
        # fixture footprint is 2 blocks x 16*8 f32 = 1024 bytes: at the
        # limit is legal, one byte under is a finding
        assert not KC.check_spec(_spec(vmem_limit=1024))
        found = KC.check_spec(_spec(vmem_limit=1023))
        assert "vmem-budget" in _rules(found)

    def test_bf16_inputs_without_upcast_flagged(self):
        found = KC.check_spec(_spec(
            dtype=jnp.bfloat16, low_precision_inputs=("a",)))
        assert "accum-dtype" in _rules(found)

    def test_bf16_inputs_with_upcast_clean(self):
        trace = lambda: jax.make_jaxpr(  # noqa: E731
            lambda x: x.astype(jnp.float32) + 1.0)(
                jax.ShapeDtypeStruct((64, 8), jnp.bfloat16))
        assert not KC.check_spec(_spec(
            dtype=jnp.bfloat16, low_precision_inputs=("a",), trace=trace))

    def test_bf16_dot_in_body_flagged(self):
        dims = (((1,), (0,)), ((), ()))
        trace = lambda: jax.make_jaxpr(  # noqa: E731
            lambda a: jax.lax.dot_general(a, a.T, dims))(
                jax.ShapeDtypeStruct((8, 8), jnp.bfloat16))
        found = KC.check_spec(_spec(dtype=jnp.bfloat16, trace=trace))
        assert "accum-dtype" in _rules(found)

    def test_shipped_kernel_specs_clean(self):
        specs = KC.all_specs()
        names = {s.name.split("[")[0] for s in specs}
        # every kernel package must export specs — a package silently
        # dropping out of all_specs() would turn the checker off for it
        assert names == {"beam_score", "beam_score_int8", "beam_score_pq",
                         "rng_prune", "pairwise_l2", "fm_interact"}, names
        for spec in specs:
            assert not KC.check_spec(spec), spec.name

    def test_grid_points_full_and_boundary(self):
        assert list(grid_points((2, 3))) == [
            (i, j) for i in range(2) for j in range(3)]
        pts = list(grid_points((1000, 1000)))
        assert len(pts) < 1000 * 1000
        assert (0, 0) in pts and (999, 999) in pts  # corners witnessed


# ----------------------------------------------------------------- repo lint

class TestRepoLint:
    def test_bare_assert_flagged(self):
        found = RL.lint_source("def f(x):\n    assert x > 0\n", "m.py")
        assert "bare-assert" in _rules(found)

    def test_assert_pragma_suppressed(self):
        src = "def f(x):\n    assert x > 0  # repo-lint: allow-assert\n"
        assert not RL.lint_source(src, "m.py")

    def test_key_reuse_flagged(self):
        src = ("import jax\n"
               "def f(key):\n"
               "    a = jax.random.normal(key, (4,))\n"
               "    b = jax.random.normal(key, (4,))\n"
               "    return a, b\n")
        found = RL.lint_source(src, "m.py")
        assert "key-reuse" in _rules(found)

    def test_split_keys_clean(self):
        src = ("import jax\n"
               "def f(key):\n"
               "    ka, kb = jax.random.split(key)\n"
               "    a = jax.random.normal(ka, (4,))\n"
               "    b = jax.random.normal(kb, (4,))\n"
               "    return a, b\n")
        assert not RL.lint_source(src, "m.py")

    def test_exclusive_branches_not_flagged(self):
        # one consumer per if/else arm: mutually exclusive, not reuse
        src = ("import jax\n"
               "def f(key, flip):\n"
               "    if flip:\n"
               "        return jax.random.normal(key, (4,))\n"
               "    else:\n"
               "        return jax.random.uniform(key, (4,))\n")
        assert not RL.lint_source(src, "m.py")

    def test_hardcoded_interpret_flagged(self):
        src = "def f(k):\n    return k(interpret=True)\n"
        found = RL.lint_source(src, "m.py")
        assert "hardcoded-interpret" in _rules(found)

    def test_interpret_pragma_and_nonliteral_clean(self):
        src = ("def f(k, mode):\n"
               "    a = k(interpret=True)  # repo-lint: allow-interpret\n"
               "    return a, k(interpret=mode)\n")
        assert not RL.lint_source(src, "m.py")

    def test_syntax_error_reported_not_raised(self):
        found = RL.lint_source("def f(:\n", "m.py")
        assert "syntax-error" in _rules(found)

    def test_library_tree_clean(self):
        # satellite contract: the shipped baseline is empty, so src/repro
        # itself must lint clean
        found = RL.run(log=_SILENT)
        fresh = B.new_findings(found, B.load_baseline())
        assert not fresh, [str(f) for f in fresh]


# ----------------------------------------------------------- baseline + CLI

class TestBaselineAndCLI:
    def test_baseline_round_trip(self, tmp_path):
        path = tmp_path / "BASELINE.json"
        f1 = B.Finding("lint", "bare-assert", "m.py:3", "detail a")
        f2 = B.Finding("jaxpr", "wide-dtype", "entry:mul", "detail b")
        B.write_baseline([f1, f2, f1], path)          # duplicate collapses
        base = B.load_baseline(path)
        assert base == {f1.key, f2.key}
        f3 = B.Finding("kernel", "vmem-budget", "spec", "")
        fresh = B.new_findings([f1, f3, f3, f2], base)
        assert [f.key for f in fresh] == [f3.key]     # deduped, stable order

    def test_missing_baseline_is_empty(self, tmp_path):
        assert B.load_baseline(tmp_path / "nope.json") == set()

    def test_cli_lint_pass_clean(self, capsys):
        assert cli_main(["--passes", "lint", "--check-baseline", "-q"]) == 0
        assert "0 new" in capsys.readouterr().out

    def test_cli_gate_fails_on_seeded_finding(self, tmp_path, monkeypatch,
                                              capsys):
        # end-to-end CI-gate proof: seed one violation, watch the gate
        # fail, baseline it, watch the gate pass
        seeded = B.Finding("lint", "bare-assert", "repro/fx.py:1", "seeded")
        monkeypatch.setattr(RL, "run", lambda log=print: [seeded])
        path = tmp_path / "BASELINE.json"
        args = ["--passes", "lint", "--baseline", str(path), "-q"]
        assert cli_main(args + ["--check-baseline"]) == 1
        assert f"NEW {seeded}" in capsys.readouterr().out
        assert cli_main(args + ["--write-baseline"]) == 0
        assert cli_main(args + ["--check-baseline"]) == 0

    def test_cli_without_gate_reports_but_passes(self, monkeypatch):
        seeded = B.Finding("lint", "bare-assert", "repro/fx.py:1", "seeded")
        monkeypatch.setattr(RL, "run", lambda log=print: [seeded])
        assert cli_main(["--passes", "lint", "-q"]) == 0

    def test_cli_rejects_unknown_pass(self):
        with pytest.raises(SystemExit):
            cli_main(["--passes", "nonsense"])


# ---------------------------------------------------------- recompile guard

@pytest.mark.skipif(not os.environ.get("BENCH_SMOKE"),
                    reason="executes a real streaming churn (BENCH_SMOKE=1)")
def test_recompile_guard_contract():
    from repro.analysis import recompile_guard as RG

    found = RG.run(log=_SILENT)
    assert not found, [str(f) for f in found]
