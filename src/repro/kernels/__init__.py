"""Pallas TPU kernels for the framework's compute hot spots.

Three kernels, each a `<name>/` subpackage with:

* ``kernel.py`` — the pl.pallas_call body with explicit BlockSpec VMEM tiling
* ``ops.py``    — the jit'd public wrapper (padding, reshaping, GQA mapping)
* ``ref.py``    — the pure-jnp oracle the tests sweep against

1. ``fused_filter_agg`` — the paper's 4.4.2 optimization as a single VMEM
   pass: predicate + masked grouped aggregation without materializing the
   filtered intermediate.  TPU adaptation of a row-wise CPU pipeline:
   one-hot compare against the group ids laid along sublanes, per-lane
   partial sums on the VPU, block-accumulated over a sequential grid (no
   scatter, no MXU pass that would round values to bf16).
2. ``flash_attention`` — blockwise online-softmax causal attention
   (training + prefill), with optional sliding window (SWA archs).
3. ``decode_attention`` — single-token attention against a long KV cache,
   S-blocked with running-max/denominator accumulators (serving).

Mosaic compiles the kernels on a TPU; on the CPU, where the tests run,
they run in the Pallas interpreter.  ``runtime/device.py`` decides which
from JAX's backend — no caller passes it.  ``tests/test_tpu_compile.py``
compiles each kernel for a described v5e at real widths, and
``chip_smoke.py`` runs the query kernel on the chip.

Routing (when does a query actually hit ``fused_filter_agg``?)
--------------------------------------------------------------
Since SQL v2 the kernel is wired into the query engine: the planner
(``core/physical.py``) and the interactive path (``Runner.query``) ask
``engine/route.py`` for a :class:`RouteDecision` per aggregation query.
Under the default ``engine="auto"`` a query routes to the kernel only
when the decision is *provably byte-identical* to the jnp reference:

* shape: exactly one GROUP BY key, aggregates ⊆ {COUNT, SUM, MEAN}, and
  non-COUNT aggregate arguments are plain column references;
* key: integer/bool dtype with shard-stats min/max known and a value
  range ≤ 1024 groups (LEFT JOINs widen the range to include the 0
  fill value);
* exactness: all values integer-typed and small enough that their f32
  sums stay exact (< 2^24) — float columns never auto-route because
  f32 re-association changes low bits;
* filter: fused natively only for a single ``col <op> literal`` whose
  column stats prove f32-exact compare; any other predicate is
  evaluated by the jnp expression tree and fed to the kernel as a mask.

``engine="kernel"`` forces the route (structural impossibility raises
``RouteError``); ``engine="jnp"`` pins the reference path.  The kernel
route is never part of node fingerprints — both engines produce
byte-identical artifacts, so cache entries stay warm across engine
switches.  A jnp-routed aggregation whose keys the statistics bound runs
the dense group-by instead, whose float sums differ from the reference
in the low bits; a node that takes it and sums names its group path in
its fingerprint (``engine/route.py``, ``reassociates``).
"""
