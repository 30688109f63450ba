"""Static program auditor: prove the serving stack's invariants WITHOUT
executing a single slot.

Every contract the episode/serving stack leans on — zero per-slot
transfers, zero recompiles across the (method x bucket) matrix, donated
slot-step buffers, fixed executable signatures — was previously proven
only at runtime (transfer guards, compile counters, differential suites
that execute whole episodes).  This package re-derives those contracts
statically, in seconds, from traces/lowerings over abstract
``ShapeDtypeStruct`` arguments (the ``launch/dryrun.py`` pattern): no
fake devices, no episode execution, nothing runs.

Three passes
------------
``repro.analysis.jaxpr_audit`` (CLI: ``python -m repro.analysis.jaxpr_audit``)
    Traces every audited executable (see ``programs``) to a ClosedJaxpr
    and walks it, recursing into scan/while/cond/pjit sub-jaxprs:

    * **no-host-callback** — timed scopes contain no ``*_callback``,
      ``debug_*``, infeed/outfeed, or host-memory ``device_put``
      primitives (the static form of the runtime transfer guard);
    * **donation** — the unified slot-step's lowering marks exactly the
      frames/gt_boxes/gt_valid argument leaves as donated
      (``lowered.args_info``), and episode programs donate nothing
      (their carries are reused across windows);
    * **two-harvest** — each episode jaxpr emits exactly TWO
      slot-stacked outputs (the (T, 2, C) log pack + the (T, 4) control
      pack): the "exactly 2 harvest fetches per run, slot-count
      independent" contract, derived from the program itself;
    * **fleet-size-independent PRNG** — ``fleet.slot_camera_keys``
      lowers to an identical primitive multiset at different camera
      counts (a pure per-(slot, camera) fold-in, no per-camera split
      chain), so adding cameras can never perturb another camera's
      noise stream;
    * **matrix-count** — the audited episode registry enumerates exactly
      ``len(METHODS) x len(EPISODE_BUCKETS)`` executables, the
      zero-mid-suite-recompile budget the harness asserts at runtime.

``repro.analysis.manifest`` (CLI: ``python -m repro.analysis.manifest``)
    Canonical JSON fingerprint per executable — signature hash, arg
    shapes/dtypes, donated leaf indices, static flops/bytes from
    ``cost_analysis()``, memory footprint from ``memory_analysis()`` —
    pinned at ``tests/golden/executable_manifest.json``.  Any signature
    drift (i.e. a future recompile) fails the audit lane before any
    test executes an episode.  Regenerate ONLY via
    ``python -m repro.analysis.manifest --write`` on an intentional
    program change, and say so in the PR.

``repro.analysis.lint`` (CLI: ``python -m repro.analysis.lint``)
    AST pass over ``src/repro/`` enforcing the tracing rules inside the
    registered traced scopes (no runtime import of the linted modules):

    ===============  ========================================================
    rule id          fires on
    ===============  ========================================================
    ``host-sync``    ``.item()`` / ``float()`` / ``int()`` / ``np.asarray``
                     / ``jax.device_get`` / ``block_until_ready`` inside a
                     traced scope — each is a device sync (or a trace-time
                     concretization error waiting to happen)
    ``traced-branch``  Python ``if``/``while`` on a value produced by a
                     ``jnp``/``jax``/``lax`` call in the same scope —
                     host control flow on traced data
    ``unseeded-rng``  global-state RNG (``np.random.<dist>``, seedless
                     ``np.random.default_rng()``, stdlib ``random.*``) —
                     every stream must derive from an explicit seed/key
    ===============  ========================================================

Traced-scope registry
---------------------
``lint.TRACED_SCOPES`` maps repo paths (relative to ``src/repro``) to
the function names whose bodies are traced (or host-adjacent enough
that a sync inside them must be justified); ``"*"`` marks a whole
module.  Current registry: the fleet slot/control/episode impls
(``core/fleet.py``), the traced elastic controller (``core/elastic.py``),
all of ``core/codec.py``, the episode body ``run_episode`` in
``core/scheduler.py``, the utility-MLP traced paths + ``fit`` in
``core/utility.py``, the device allocators + table builder in
``core/allocation.py``, and the window dispatch in ``serve/stream.py``.

Pragma grammar
--------------
A justified exception carries an inline pragma on the offending line or
the line directly above it::

    loss = float(loss)  # audit: allow(host-sync) one sync at fit() end

or on (or directly above) a ``def`` line, covering that whole
function::

    # audit: allow(host-sync) host reference path, one designed fetch
    def build_utility_table(...):

The rule id in parentheses must match the violated rule exactly; a
bare ``# audit: allow`` matches nothing.  Keep the one-line
justification after the pragma — the lint battery asserts pragmas
stay attached to the rules they suppress.
"""
