"""AOT-exported program bank: zero-compile restart and cold start.

Every recovery path the resilience stack earned — supervisor retry,
gang/fleet rank respawn, autoscaled replicas — still pays the full
bank/warm phase (tens of seconds to minutes of compilation) before its
first dispatch, so MTTR is dominated by recompilation rather than by
the failure itself.  The universal interpreter made the program family
CLOSED and tiny (ROADMAP §5/§9), which is exactly the precondition for
serializing it: this module persists each compiled executable next to
the persistent XLA cache so a cold or restarted process DESERIALIZES
programs instead of compiling them, in the compile-once-ship-everywhere
mold of "Automatic Full Compilation ... to Cloud TPUs" (PAPERS.md,
1810.09868) — with BEAGLE 4.1's cross-architecture packaging caution
applied as hard version/fingerprint keying rather than hope.

Mechanism
---------
* **Artifact** = one serialized compiled executable per family x
  jit-key bucket: `jax.experimental.serialize_executable` pickles the
  UNLOADED PjRt executable (plus its arg/result pytrees), which —
  unlike a `jax.export` StableHLO module, which must still be XLA-
  compiled at load — reloads with ZERO compile work.  The price is
  version lock-in, so every artifact is stamped with the jax/jaxlib
  versions, the `jax.export` calling-convention version, this bank's
  own ABI ordinal, the backend platform build string, and the PR2
  host-feature fingerprint; any mismatch is a load REJECTION, never a
  deserialization attempt.
* **Bank directory** = `<persistent cache partition>/export_bank/`,
  artifacts staged + fsync'd + atomically renamed (GL007), each
  recorded in the partition's `bank_manifest.json` under `"exports"`
  with a content digest.
* **Load ladder** (per program, at first dispatch of each jit-key
  bucket): exported artifact -> persistent-XLA-cache compile ->
  fresh compile.  EVERY load failure — version/ABI skew, fingerprint
  mismatch, truncated or corrupt artifact, deserialize exception,
  avals drift between the caller and the compiled signature — falls
  through to the next rung with an explicit counter
  (`bank.export.{hits,misses,corrupt,rejected.<reason>}`) and a ledger
  event, and a rejected artifact is QUARANTINED (renamed aside, its
  manifest entry dropped) so it cannot re-fail every restart.  The
  fall-through is a counter-carrying downgrade to the normal bank
  phase, not a distinct failure cause: nothing in this module may
  crash a run.

`EXAML_EXPORT_BANK` = `off` (default) / `on` / `require`.  The bank is
opt-in like the other measured tiers (EXAML_FLEET_UNIBATCH,
EXAML_CLV_DTYPE): serialized executables are pinned to one
jaxlib+platform build, and the per-dispatch signature lookup costs a
few microseconds of host time, so the operator enables it per
deployment (serving fleets, supervised long runs, autoscaled
replicas).  `require` turns any fall-through into a hard error — the
CI cold-start gate's mode, proving the zero-compile path end to end.
The mode is read when a program is CREATED (engine construction), not
per dispatch.

Scope: single-process, default-device engines.  Mesh-sharded and -S
(SEV) program variants keep the in-process compile path (ROADMAP §4:
their executables embed mesh/device state this bank does not attempt
to relocate); `engine.first_calls.inprocess_sharded` keeps counting
that residual exposure.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
from typing import Callable, Dict, Optional

from examl_tpu import obs

ENV_VAR = "EXAML_EXPORT_BANK"
DIR_NAME = "export_bank"
ARTIFACT_SUFFIX = ".jexe"
QUARANTINE_SUFFIX = ".quarantined"

# Bump when the artifact layout or the wrapper's signature derivation
# changes: an old artifact must REJECT (rejected.abi), not deserialize
# into a wrong calling convention.
EXPORT_ABI = 1

# Process state: the in-memory loaded-executable memo (several engines
# with identical shapes — bench builds many — share one deserialize).
# One run = one record: cli.main resets alongside bank.reset().
_STATE: Dict[str, object] = {"mem": {}}


class ExportBankRequired(RuntimeError):
    """EXAML_EXPORT_BANK=require and a program could not be served from
    an exported artifact — the CI gate for the zero-compile path."""


def reset() -> None:
    """Drop loaded-executable memos (one run = one export-bank record;
    in-process callers invoking the CLI repeatedly must not serve a
    previous run's deserialized executables past an env change)."""
    _STATE["mem"] = {}


def mode() -> str:
    """"off" | "on" | "require" from EXAML_EXPORT_BANK.  Loud on typos
    (matching EXAML_CLV_DTYPE): a silently-misspelled opt-in would run
    every restart cold while the operator believes otherwise."""
    v = (os.environ.get(ENV_VAR) or "").strip().lower()
    if v in ("", "0", "off", "no"):
        return "off"
    if v in ("1", "on", "yes"):
        return "on"
    if v == "require":
        return "require"
    raise ValueError(f"{ENV_VAR}={v!r}: expected off/on/require")


def enabled() -> bool:
    try:
        return mode() != "off"
    except ValueError:
        return False


def bank_dir(create: bool = False) -> Optional[str]:
    """The exported-artifact directory inside the CURRENT persistent
    cache partition (config.persistent_cache_dir), or None when no
    cache is configured — the export bank shares the cache's
    platform+fingerprint scoping, so a host that must not share
    compiled code cannot share artifacts either."""
    from examl_tpu.config import persistent_cache_dir
    cache = persistent_cache_dir()
    if not cache:
        return None
    d = os.path.join(cache, DIR_NAME)
    if create:
        try:
            os.makedirs(d, exist_ok=True)
        except OSError:
            return None
    return d if os.path.isdir(d) else (None if not create else d)


def host_meta() -> dict:
    """The version/ABI/fingerprint stamp every artifact carries and
    every load must match."""
    import jax
    import jaxlib

    from examl_tpu import config as _config

    meta = {"abi": EXPORT_ABI, "format": "pjrt-pickle-v1",
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "calling_convention": _calling_convention()}
    try:
        dev = jax.devices()[0]
        meta["platform"] = dev.platform
        meta["platform_version"] = getattr(dev.client,
                                           "platform_version", "?")
    except Exception:                        # noqa: BLE001
        meta["platform"] = meta["platform_version"] = "?"
    meta["fingerprint"] = _config.host_feature_fingerprint() or ""
    return meta


def _calling_convention() -> Optional[int]:
    """jax.export's calling-convention version — recorded so a future
    jax that changes the exported ABI rejects by stamp, not by crash."""
    try:
        from jax import export as _jexport
        for attr in ("maximum_supported_calling_convention_version",
                     "maximum_supported_serialization_version"):
            v = getattr(_jexport, attr, None)
            if v is not None:
                return int(v)
    except Exception:                        # noqa: BLE001
        pass
    return None


def _meta_reject_reason(entry: dict, meta: dict) -> Optional[str]:
    """First mismatching stamp of a manifest entry vs this process, or
    None when the artifact is admissible."""
    if entry.get("abi") != meta["abi"] or \
            entry.get("format") != meta["format"] or \
            entry.get("calling_convention") != meta["calling_convention"]:
        return "abi"
    if entry.get("jax") != meta["jax"] or \
            entry.get("jaxlib") != meta["jaxlib"]:
        return "version"
    if entry.get("platform") != meta["platform"] or \
            entry.get("platform_version") != meta["platform_version"]:
        return "platform"
    if entry.get("fingerprint") != meta["fingerprint"]:
        return "fingerprint"
    return None


# ---------------------------------------------------------------------------
# manifest: the "exports" section of bank_manifest.json


def _manifest_path(d: Optional[str] = None) -> Optional[str]:
    d = d or bank_dir()
    if not d:
        return None
    from examl_tpu.ops.bank import MANIFEST_NAME
    return os.path.join(os.path.dirname(d), MANIFEST_NAME)


def read_exports(d: Optional[str] = None) -> Dict[str, dict]:
    """{sig: artifact entry} from the partition's bank manifest."""
    path = _manifest_path(d)
    if not path:
        return {}
    try:
        with open(path) as f:
            return dict(json.load(f).get("exports") or {})
    except (OSError, ValueError):
        return {}


def _update_exports(mutate: Callable[[Dict[str, dict]], None]) -> None:
    """Read-modify-write the manifest's exports section, staged +
    fsync'd + atomically renamed (GL007): a crash mid-update must never
    publish a torn manifest, since every later restart's load ladder
    reads it.  Other manifest sections (families, chunk_layout) are
    preserved verbatim, and the read-modify-write holds an advisory
    flock: the `--bank` compile workers export their families in
    PARALLEL processes, and an unlocked RMW would silently drop a
    concurrent worker's entries (its artifacts would then re-export on
    the next populate — correct but wasteful)."""
    path = _manifest_path()
    if not path:
        return
    lock_fd = None
    try:
        try:
            import fcntl
            lock_fd = os.open(path + ".lock",
                              os.O_CREAT | os.O_RDWR, 0o644)
            fcntl.flock(lock_fd, fcntl.LOCK_EX)
        except Exception:                    # noqa: BLE001 — advisory
            lock_fd = None
        doc = {}
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
        if not isinstance(doc, dict):
            doc = {}
        doc.setdefault("version", 1)
        exports = dict(doc.get("exports") or {})
        mutate(exports)
        doc["exports"] = exports
        doc["updated"] = time.time()
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except OSError as exc:
        obs.log(f"EXAML: export bank: manifest update failed ({exc}); "
                "artifacts remain usable from their own stamps on the "
                "next successful write")
    finally:
        if lock_fd is not None:
            try:
                os.close(lock_fd)            # releases the flock
            except OSError:
                pass


def family_coverage(families=None, ntaxa=None) -> Dict[str, int]:
    """{family: artifact count} of admissible exported artifacts — the
    signal `bank.run_bank` uses to SKIP subprocess compile workers for
    families a cold restart will deserialize instead.

    Runs BEFORE the parent touches its backend (the bank's ordering
    contract on exclusive-access accelerators), so the platform build
    string is not yet knowable: admissibility here checks the
    backend-independent stamps (ABI, jax/jaxlib, host fingerprint) and
    scans every cache partition for this host.  A partition whose
    platform later disagrees costs a rejected-artifact fall-through to
    the watchdogged in-process compile — bounded and counted, never
    wrong results.

    `ntaxa` (when the caller can derive it pre-backend, e.g. from the
    byteFile header) filters out artifacts exported from a DIFFERENT
    dataset: artifact loadability is signature-level (avals), so
    name-level coverage from another dataset's artifacts would skip
    compile workers only to miss at warm time.  Same-taxa datasets
    with different pattern widths remain a residual (bounded by the
    watchdogged in-process compile and the hits==0 evidence)."""
    if not enabled():
        return {}
    from examl_tpu.config import host_feature_fingerprint
    from examl_tpu.ops.bank import MANIFEST_NAME

    import jax.version as _jv
    import jaxlib.version as _jlv
    fp = host_feature_fingerprint() or ""
    want = None if families is None else set(families)
    cover: Dict[str, int] = {}
    for mpath in _candidate_manifests(MANIFEST_NAME):
        try:
            with open(mpath) as f:
                exports = json.load(f).get("exports") or {}
        except (OSError, ValueError):
            continue
        for entry in exports.values():
            fam = entry.get("family")
            if not fam or (want is not None and fam not in want):
                continue
            if entry.get("abi") != EXPORT_ABI:
                continue
            if entry.get("jax") != _jv.__version__ or \
                    entry.get("jaxlib") != _jlv.__version__:
                continue
            if entry.get("fingerprint") != fp:
                continue
            if ntaxa is not None and entry.get("ntips") is not None \
                    and entry["ntips"] != ntaxa:
                continue
            cover[fam] = cover.get(fam, 0) + 1
    return cover


def _candidate_manifests(manifest_name: str):
    """Manifest paths to scan pre-backend: the configured partition if
    jax already knows one, else every partition under the cache root
    (the per-entry stamps do the host filtering)."""
    from examl_tpu.config import persistent_cache_dir
    cache = persistent_cache_dir()
    if cache:
        p = os.path.join(cache, manifest_name)
        return [p] if os.path.exists(p) else []
    from examl_tpu.config import compile_cache_root
    root = compile_cache_root()
    if root is None:
        return []
    out = []
    try:
        # The root itself (accelerators, or JAX's own directory) and
        # its per-host CPU partitions.
        for d in [root] + [os.path.join(root, sub)
                           for sub in sorted(os.listdir(root))]:
            p = os.path.join(d, manifest_name)
            if os.path.exists(p):
                out.append(p)
    except OSError:
        pass
    return out


def artifact_count() -> int:
    return len(read_exports())


def startup_info() -> str:
    """One info-file line for CLI startup: where the bank lives and how
    much of it is admissible right now."""
    from examl_tpu.config import persistent_cache_dir
    if not persistent_cache_dir():
        # Distinct from "bank dir not created yet": the first populate
        # run legitimately has no export_bank/ subdirectory until its
        # first artifact stages one.
        return ("exported program bank: enabled, but no persistent "
                "cache partition is configured — artifacts cannot "
                "persist (set EXAML_COMPILE_CACHE)")
    d = bank_dir(create=True)
    cover = family_coverage()
    return (f"exported program bank: {d} ({artifact_count()} artifacts, "
            f"{len(cover)} admissible families, mode {mode()})")


# ---------------------------------------------------------------------------
# signature: family x jit-key bucket -> stable artifact id


def _never() -> bool:
    return False


def jax_leaves(args) -> list:
    import jax
    return jax.tree_util.tree_leaves(args)


def _leaf_sig(leaf) -> tuple:
    shape = getattr(leaf, "shape", None)
    if shape is None:
        # Python scalars trace as weak-typed 0-d avals: the executable
        # is value-independent, so the TYPE is the whole signature.
        return (type(leaf).__name__,)
    return (tuple(shape), str(getattr(leaf, "dtype", "?")),
            bool(getattr(leaf, "weak_type", False)))


def _route_key(args) -> tuple:
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef, tuple(_leaf_sig(leaf) for leaf in leaves))


def signature(static_key: str, rkey: tuple) -> str:
    """Stable hex id of one program: the engine's program-identity
    constants + jit-cache key (`static_key`, already repr'd) and the
    flattened arg avals.  Identical run configs derive identical
    signatures in different processes — that is the whole point."""
    treedef, leafs = rkey
    text = "|".join((static_key, str(treedef), repr(leafs)))
    return hashlib.sha1(text.encode()).hexdigest()[:20]


# ---------------------------------------------------------------------------
# load ladder


def _ledger(status: str, family: str, sig: str, **fields) -> None:
    obs.ledger_event("export", status=status, family=family, sig=sig,
                     **fields)


def _quarantine(entry: dict, family: str, sig: str, reason: str) -> None:
    """Rename a rejected artifact aside and drop its manifest entry so
    it cannot re-fail every restart; the quarantined file stays on disk
    for postmortems."""
    d = bank_dir()
    fname = entry.get("file") if entry else None
    if d and fname:
        path = os.path.join(d, fname)
        try:
            if os.path.exists(path):
                # graftlint: disable=GL007 -- atomicity-only rename of
                # an already-rejected artifact; its content is exactly
                # what we refuse to trust, so durability adds nothing
                os.replace(path, path + QUARANTINE_SUFFIX)
        except OSError:
            pass
    _update_exports(lambda ex: ex.pop(sig, None))
    obs.inc("bank.export.quarantined")
    _ledger("quarantined", family, sig, reason=reason)
    obs.log(f"EXAML: export bank: artifact for family '{family}' "
            f"({sig}) rejected ({reason}) and quarantined; the program "
            "falls back to the persistent-cache/compile rung")


def _reject(reason: str, family: str, sig: str,
            entry: Optional[dict] = None, quarantine: bool = True) -> None:
    obs.inc(f"bank.export.rejected.{reason}")
    _ledger("rejected", family, sig, reason=reason)
    if quarantine and entry is not None:
        _quarantine(entry, family, sig, reason)
    elif entry is not None and reason == "missing":
        # Stale manifest entry pointing at a deleted artifact: nothing
        # to quarantine — just stop advertising it.
        _update_exports(lambda ex: ex.pop(sig, None))


def load(family: str, sig: str):
    """One rung of the ladder: the deserialized executable for `sig`,
    or None after counting exactly why.  Never raises — any failure
    (including an armed `bank.export.load` fault) is a fall-through."""
    mem = _STATE["mem"]
    if sig in mem:
        return mem[sig]
    try:
        with obs.timer("bank.export_load_seconds"):
            loaded = _load_uncached(family, sig)
    except Exception as exc:                 # noqa: BLE001 — incl. faults
        obs.inc("bank.export.rejected.error")
        _ledger("rejected", family, sig, reason="error",
                error=f"{type(exc).__name__}: {exc}"[:200])
        return None
    if loaded is not None:
        mem[sig] = loaded
        # Program observatory (obs/programs.py): a deserialized
        # executable answers cost/memory analysis directly, so a
        # zero-compile cold start (engine.compile_count == 0, the
        # guard never fires) still gets its registry row — source
        # "exported", compile seconds 0 by construction.
        from examl_tpu.obs import programs as _programs
        _programs.record_loaded(family, sig, loaded)
    return loaded


def _load_uncached(family: str, sig: str):
    from examl_tpu.resilience import faults
    faults.fire("bank.export.load")
    d = bank_dir()
    if d is None:
        obs.inc("bank.export.misses")
        return None
    entry = read_exports(d).get(sig)
    if entry is None:
        obs.inc("bank.export.misses")
        _ledger("miss", family, sig)
        return None
    reason = _meta_reject_reason(entry, host_meta())
    if reason is not None:
        _reject(reason, family, sig, entry)
        return None
    path = os.path.join(d, entry.get("file") or "")
    if not entry.get("file") or not os.path.exists(path):
        _reject("missing", family, sig, entry, quarantine=False)
        return None
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        _reject("missing", family, sig, entry, quarantine=False)
        return None
    if hashlib.sha256(blob).hexdigest() != entry.get("digest"):
        # Truncated writes and flipped manifest digests both land here:
        # either way the bytes are not the bytes the stamp promised.
        _reject("digest", family, sig, entry)
        return None
    try:
        from jax.experimental import serialize_executable as _se
        rec = pickle.loads(blob)
        loaded = _se.deserialize_and_load(rec["payload"], rec["in_tree"],
                                          rec["out_tree"])
    except Exception as exc:                 # noqa: BLE001
        obs.inc("bank.export.corrupt")
        _ledger("rejected", family, sig, reason="corrupt",
                error=f"{type(exc).__name__}: {exc}"[:200])
        _quarantine(entry, family, sig, "corrupt")
        return None
    obs.inc("bank.export.hits")
    _ledger("hit", family, sig)
    return loaded


# ---------------------------------------------------------------------------
# export


def export(lowered, family: str, sig: str,
           entry_meta: Optional[dict] = None) -> bool:
    """Serialize one program into the bank: compile the traced lowering
    with the persistent XLA cache BYPASSED, pickle the unloaded
    executable, verify it deserializes, stage + fsync + rename, record
    the manifest entry.  Failures only forfeit the artifact
    (`bank.export.write_errors`); the run already has its compiled
    program.

    The cache bypass is load-bearing, not an optimization miss: an
    XLA:CPU executable that was itself LOADED from the compilation
    cache re-serializes into a blob whose JIT'd symbols are absent
    ("Symbols not found" at deserialize — measured on jaxlib 0.4.36),
    so the artifact must come from a genuinely fresh compile.  That
    one extra compile is paid once per artifact lifetime, in the
    populate run, off every restart's critical path — exactly the
    trade this bank exists to make.  The pre-publish verify makes the
    guarantee local: a blob that cannot deserialize HERE is never
    published to fail on some future cold start."""
    d = bank_dir(create=True)
    if d is None:
        return False
    t0 = time.perf_counter()
    try:
        from examl_tpu.resilience import faults
        faults.fire("bank.export.write")
        import jax
        from jax.experimental import serialize_executable as _se
        # The export compile must be HERMETIC: an executable the
        # persistent-cache machinery has touched — serialized for a
        # cache write, or deserialized from a cache hit — re-serializes
        # into a blob whose JIT'd symbols are gone ("Symbols not found"
        # at deserialize; measured on XLA:CPU, jaxlib 0.4.36).  So for
        # the duration of this one compile the cache is fully torn down
        # (reset_cache drops the dir-pinned singleton — a plain config
        # update is IGNORED by an already-initialized cache) and the
        # no-op compiler option (explicitly its default value: codegen
        # and numerics untouched) busts jax's in-memory compile memo,
        # which would otherwise hand back the guarded call's
        # cache-tainted executable.  The verify below gates
        # publication either way.
        prior_cache = jax.config.jax_compilation_cache_dir
        _cc = None
        try:
            from jax._src import compilation_cache as _cc
        except Exception:                    # noqa: BLE001
            _cc = None

        def _drop_cache_singleton():
            # Guarded separately: a future jax renaming reset_cache
            # must degrade to "export without the teardown" (verify
            # still gates publication), never leave the restore half
            # of the try/finally unreached.
            if _cc is not None:
                try:
                    _cc.reset_cache()
                except Exception:            # noqa: BLE001
                    pass

        try:
            jax.config.update("jax_compilation_cache_dir", None)
            _drop_cache_singleton()
            try:
                compiled = lowered.compile(compiler_options={
                    "xla_embed_ir_in_executable": False})
            except Exception:                # noqa: BLE001 — backends
                # that reject the option (non-CPU compilers) fall back
                # to a plain AOT compile; verify still gates.
                compiled = lowered.compile()
        finally:
            jax.config.update("jax_compilation_cache_dir", prior_cache)
            # Next cache use re-initializes against the restored dir;
            # nothing on disk was touched.
            _drop_cache_singleton()
        payload, in_tree, out_tree = _se.serialize(compiled)
        _se.deserialize_and_load(payload, in_tree, out_tree)  # verify
        blob = pickle.dumps({"payload": payload, "in_tree": in_tree,
                             "out_tree": out_tree},
                            protocol=pickle.HIGHEST_PROTOCOL)
        fname = f"{family}-{sig}{ARTIFACT_SUFFIX}"
        path = os.path.join(d, fname)
        # pid-suffixed stage (like the manifest RMW): two fleet ranks
        # exporting the same signature concurrently must never share a
        # stage file — a truncating reopen would publish a torn blob.
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        entry = dict(host_meta(), family=family, file=fname,
                     digest=hashlib.sha256(blob).hexdigest(),
                     size=len(blob), created=time.time(),
                     **(entry_meta or {}))
        _update_exports(lambda ex: ex.__setitem__(sig, entry))
        obs.inc("bank.export.writes")
        obs.observe("bank.export_write_seconds",
                    time.perf_counter() - t0)
        _ledger("written", family, sig, bytes=len(blob))
        return True
    except Exception as exc:                 # noqa: BLE001 — incl. faults
        obs.inc("bank.export.write_errors")
        _ledger("write_error", family, sig,
                error=f"{type(exc).__name__}: {exc}"[:200])
        obs.log(f"EXAML: export bank: serializing family '{family}' "
                f"failed ({type(exc).__name__}: {exc}); the run keeps "
                "its compiled program, only the artifact is lost")
        return False


# ---------------------------------------------------------------------------
# the dispatch wrapper (the engine's program-creation seams call this)


def wrap(raw_fn, fallback, family: str, static_key,
         exportable: bool = True, entry_meta: Optional[dict] = None):
    """Route a jitted program through the export bank.

    `raw_fn` is the bare `jax.jit` callable (used for `.lower()` at
    export time — tracing only, before any donation), `fallback` the
    watchdog-guarded callable the engine would otherwise install.  Per
    distinct arg signature (= jit-key bucket) the FIRST dispatch
    resolves the ladder: a loadable artifact serves every later call
    with zero compiles and the compile watchdog never fires; a miss
    dispatches the guarded fallback (persistent-XLA-cache rung) and
    then serializes the freshly-compiled program for the next restart.

    Returns `fallback` unchanged when the bank is off or the program is
    ineligible (sharded / SEV / off-default-device engines), so the
    steady-state dispatch path pays nothing it did not opt into."""
    m = mode()                    # read at program creation, loud on typos
    if m == "off" or not exportable:
        return fallback
    skey = repr(static_key)
    routes: Dict[tuple, Callable] = {}

    def _resolve(rkey):
        sig = signature(skey, rkey)
        # Memory admission before deserialization: loading an exported
        # executable mints device buffers, so when the governor denies
        # the family's predicted peak the ladder falls through to the
        # guarded compile rung — whose cache_put seam evicts cold
        # programs first instead of stacking a fresh load on a full
        # device.  (`require` mode outranks the governor: an explicit
        # zero-compile contract must fail loudly, not quietly compile.)
        from examl_tpu.resilience import memgov
        if m != "require" and not memgov.admit_program(
                family, seam="export_bank.load"):
            return fallback
        loaded = load(family, sig)
        if loaded is not None:
            def first_hit(*args):
                try:
                    out = loaded(*args)
                except TypeError as exc:
                    # Avals drift: the artifact's compiled signature no
                    # longer matches what this run dispatches (layout
                    # knob change, schedule drift).  The check fires
                    # before execution, so donated buffers are intact
                    # for the fallback.
                    _reject("avals_drift", family, sig,
                            read_exports().get(sig), quarantine=True)
                    obs.log("EXAML: export bank: avals drift on family "
                            f"'{family}' ({type(exc).__name__}); "
                            "falling back to compile")
                    routes[rkey] = fallback
                    return fallback(*args)
                except Exception as exc:     # noqa: BLE001
                    # Environment errors (device placement, runtime
                    # init): not the artifact's fault — reject without
                    # quarantine so a healthy host keeps it.  Retry via
                    # the compile fallback ONLY if the failure happened
                    # before execution donated any input buffer: a
                    # mid-execution fault leaves donated args deleted,
                    # and re-dispatching them would crash with a
                    # misleading secondary error — that fault is a
                    # genuine device error and must propagate as
                    # itself (matching the engine's own semantics for
                    # post-donation runtime faults).
                    _reject("error", family, sig, quarantine=False)
                    obs.log("EXAML: export bank: loaded program for "
                            f"family '{family}' failed to run "
                            f"({type(exc).__name__}: {exc}); falling "
                            "back to compile")
                    routes[rkey] = fallback
                    if any(getattr(a, "is_deleted", _never)()
                           for a in jax_leaves(args)):
                        raise
                    return fallback(*args)
                routes[rkey] = loaded
                return out
            return first_hit
        if m == "require":
            raise ExportBankRequired(
                f"{ENV_VAR}=require but program family '{family}' "
                f"(signature {signature(skey, rkey)}) has no loadable "
                "exported artifact")
        if bank_dir(create=True) is None:
            return fallback

        def miss_route(*args):
            lowered = None
            try:
                # Trace BEFORE the guarded call: lowering only reads
                # avals, and the fallback donates/consumes the buffers.
                lowered = raw_fn.lower(*args)
            except Exception as exc:         # noqa: BLE001
                obs.inc("bank.export.write_errors")
                obs.log("EXAML: export bank: lowering family "
                        f"'{family}' for export failed "
                        f"({type(exc).__name__}: {exc})")
            out = fallback(*args)
            if lowered is not None:
                export(lowered, family, sig, entry_meta=entry_meta)
            routes[rkey] = fallback
            return out
        return miss_route

    def dispatch(*args):
        rkey = _route_key(args)
        route = routes.get(rkey)
        if route is None:
            route = routes[rkey] = _resolve(rkey)
        return route(*args)

    return dispatch
