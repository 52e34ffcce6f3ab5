"""Static-analysis subsystem: invariants the test suite can't see.

The tier-1 tests prove the library computes the right numbers at test
scale. This package proves a different class of property — dtype and
resource *contracts* that only cost anything at production scale, checked
without running (or even compiling, for most passes) anything:

========== =============================================================
pass       what it proves
========== =============================================================
jaxpr      Abstract-traces every registered public entry point
           (:mod:`repro.analysis.registry`) and walks the jaxpr — incl.
           all scan/while/jit/shard_map/pallas_call sub-jaxprs — for
           f64/weak-type leaks, implicit upcasts and accumulator
           violations in distance dots, non-ordinal arithmetic on uint32
           dist keys (taint analysis from the ``dist_key`` bitcast),
           host callbacks, and CLIP-mode scatters.
kernel     Consumes the spec metadata every kernel package exports
           (:mod:`repro.kernels.spec` — built from the same
           ``block_layout()`` the ``pallas_call`` uses, so it cannot
           drift): bounds per-grid-step VMEM, evaluates every index map
           over the full grid to prove in-bounds tiles, enforces the
           f32-accumulator rule under bf16 inputs.
lint       AST lint of ``src/repro`` for banned patterns: bare asserts
           in runtime paths, PRNG key reuse inside one block, hardcoded
           ``interpret=True``.
recompile  Runs a scripted streaming-churn workload counting XLA
           backend-compile events: steady-state churn must compile
           nothing; capacity growth must stay on the O(log n)
           power-of-two schedule. (Executes real work — CI runs it
           behind BENCH_SMOKE=1.)
collectives Compiles the sharded build and bounds per-device collective
           wire bytes via :mod:`repro.launch.hlo_analysis` (needs >= 2
           devices; self-skips otherwise).
========== =============================================================

CLI
---
::

    PYTHONPATH=src python -m repro.analysis                      # default passes
    PYTHONPATH=src python -m repro.analysis --passes lint,jaxpr
    PYTHONPATH=src python -m repro.analysis --only search        # filter entries
    PYTHONPATH=src python -m repro.analysis --check-baseline     # CI gate
    PYTHONPATH=src python -m repro.analysis --write-baseline     # accept current

Default passes are ``lint,jaxpr,kernel`` (hermetic, seconds);
``recompile`` and ``collectives`` execute real device work and join via
``--passes lint,jaxpr,kernel,recompile,collectives``.

Baseline workflow
-----------------
``--check-baseline`` exits non-zero on any finding whose key
(``pass:rule:where``) is absent from ``BASELINE.json`` — so CI fails on
*new* violations while a consciously-accepted legacy finding can be
recorded with ``--write-baseline``. The shipped baseline is **empty**:
``src/repro`` is clean under every pass, and PRs are expected to keep it
that way (fix, or in the rare legitimate case suppress in place with a
``# repo-lint: allow-<rule>`` pragma and a justifying comment).

Registering new entry points
----------------------------
Any PR adding a public jitted function adds a trace thunk to
:mod:`repro.analysis.registry` (see its docstring for the 3-step
checklist); new Pallas kernels export ``kernel_spec()``/``default_specs()``
from their package, built on the module-level ``block_layout()`` their
``pallas_call`` consumes (see ``repro/kernels/beam_score`` for the
pattern).
"""
from repro.analysis.baseline import (BASELINE_PATH, Finding, load_baseline,
                                     new_findings, write_baseline)

__all__ = ["BASELINE_PATH", "Finding", "load_baseline", "new_findings",
           "write_baseline"]
