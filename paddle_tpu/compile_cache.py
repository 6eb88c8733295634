"""Persistent compile/artifact cache: content-addressed AOT executables
plus the repo-wide kernel-tuning registry (COMPILE_CACHE.md).

Reference analogue: none in the reference tree — its C++ runtime pays
program "compilation" (op list preparation) in microseconds, so it never
needed one.  Here the expensive unit is an XLA executable: every server
boot and every hot swap used to re-trace, re-lower, and re-compile every
(model, batch bucket, replica) triple, making warmup the dominant cost
of a replica-set flip (ROADMAP "Persistent compilation + artifact
cache").  The Julia-to-TPU paper (PAPERS.md) shows whole-model XLA AOT
artifacts are the right unit of reuse; this module makes them a shared,
crash-safe, cross-process store.

Store layout (root = ``FLAGS.compile_cache_dir``, default
``<checkout>/.cache/paddle_tpu`` — inside the tree, git-ignored, so a
sealed machine that copies the checkout carries it and nothing is
written outside it):

    <root>/
      aot/
        <sha256-key>/            # content address of the fingerprint
          manifest.json          # schema, fingerprint fields, crc32, nbytes
          exec.bin               # serialized jax.export Exported module
        _tmp.<key>.<pid>.<tid>/  # in-flight commit (ignored by readers)
      tuning/
        <namespace>.json         # kernel-tuning registry, one file per
                                 # kernel family ("flash_attention", ...)

jax's own persistent XLA-executable cache is placed from OUTSIDE the
program (`ensure_jax_cache`): where ``JAX_COMPILATION_CACHE_DIR`` is set
jax keeps it there and this module touches nothing; where it is unset,
it goes to the fixed ``<checkout>/.cache/jax`` — for trainers as well as
predictors.  The directory is part of jax's cache key, so it never
derives from a temp name, a pid or ``FLAGS.compile_cache_dir``.

A fingerprint is a flat JSON-able dict (program content hash, feed
shapes/dtypes, fetch names, state shapes/dtypes, device kind, jax +
library versions, AMP/AD flags); its content address is the sha256 of
the canonical JSON.  Any field changing — a new jax version, a different
device kind, a retranspiled program — lands in a different entry, which
is the whole invalidation story: nothing is ever reused across an
environment change.

Commit discipline is the checkpoint vault's (CHECKPOINT.md): write every
file into a temp dir, fsync each, fsync the dir, ``os.rename`` to the
final content-addressed name, fsync the root.  A ``kill -9`` at ANY
point leaves either a stale ``_tmp.*`` dir (swept by the next commit of
the same key) or a fully-committed entry — never a half-written entry a
reader can observe.  Chaos points (driven through
``fluid.checkpoint._chaos`` / env ``PADDLE_TPU_CHAOS``), in commit
order: ``cc_exec_written`` (entry files durable, rename pending) and
``cc_committed``; the tuning registry adds ``tuning_tmp_written``.

Readers REJECT corruption silently: a manifest that does not parse, a
CRC32 mismatch, a truncated exec.bin all count as a miss (the entry is
quarantined and the caller recompiles) — a poisoned cache must never be
able to crash a server boot.

Eviction: one size-capped LRU over the AOT entries
(``FLAGS.compile_cache_max_mb``).  Last-use is the manifest mtime
(touched on every hit); the entry just written is never the victim.
jax bounds its own cache (``jax_compilation_cache_max_size``).
"""

import binascii
import hashlib
import json
import os
import shutil
import threading
import time

__all__ = [
    "CompileCache", "cache_root", "cache_enabled", "default_cache",
    "fingerprint_key", "program_fingerprint", "environment_fingerprint",
    "stats", "stats_delta", "reset_stats", "note_compile_ms",
    "note_deserialize_ms", "note_artifact_load",
    "tuning_path", "tuning_lookup", "tuning_record", "tuning_entries",
    "verify_store", "CHAOS_POINTS", "ensure_jax_cache", "checkout_cache_dir",
    "AOT_SUBDIR", "TUNING_SUBDIR", "MANIFEST_NAME", "EXEC_NAME",
]

AOT_SUBDIR = "aot"
TUNING_SUBDIR = "tuning"
MANIFEST_NAME = "manifest.json"
EXEC_NAME = "exec.bin"
SCHEMA_VERSION = 1
CHAOS_POINTS = ("cc_exec_written", "cc_committed", "tuning_tmp_written")
_TMP_PREFIX = "_tmp."


def _ckpt():
    """The checkpoint vault module — the shared fsync/atomic-write/chaos
    helpers live there (one commit discipline, one fault surface).
    Imported lazily: this module must stay importable without dragging
    the whole fluid package in at import time."""
    from .fluid import checkpoint
    return checkpoint


# ---------------------------------------------------------------------------
# store location + process-wide counters
# ---------------------------------------------------------------------------

def checkout_cache_dir(name):
    """``<checkout>/.cache/<name>``: the fixed, git-ignored home of
    everything this package caches (checkout root = the directory that
    holds the ``paddle_tpu`` package, as native/__init__.py computes
    it)."""
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".cache", name)


def cache_root():
    """Absolute store root from FLAGS.compile_cache_dir; empty flag means
    ``<checkout>/.cache/paddle_tpu``."""
    from .flags import FLAGS
    p = FLAGS.compile_cache_dir or checkout_cache_dir("paddle_tpu")
    return os.path.abspath(os.path.expanduser(p))


def cache_enabled():
    from .flags import FLAGS
    return bool(FLAGS.compile_cache)


_stats_lock = threading.Lock()


def _zero_stats():
    return {"hits": 0, "misses": 0, "puts": 0, "evictions": 0,
            "errors": 0, "artifact_loads": 0,
            "compile_ms": 0.0, "deserialize_ms": 0.0}


_stats = _zero_stats()


def _bump(name, n=1):
    with _stats_lock:
        _stats[name] += n


def stats():
    """Process-wide cache counters (wire-encodable snapshot copy)."""
    with _stats_lock:
        out = dict(_stats)
    out["compile_ms"] = round(out["compile_ms"], 3)
    out["deserialize_ms"] = round(out["deserialize_ms"], 3)
    return out


def stats_delta(before):
    """Counter delta since a `stats()` snapshot — what ONE model load /
    hot-swap flip cost (surfaced in the load_model reply and per-model
    serving metrics)."""
    now = stats()
    return {k: round(now[k] - before.get(k, 0), 3)
            if isinstance(now[k], float) else now[k] - before.get(k, 0)
            for k in now}


def reset_stats():
    global _stats
    with _stats_lock:
        _stats = _zero_stats()


def note_compile_ms(ms):
    _bump("compile_ms", float(ms))


def note_deserialize_ms(ms):
    _bump("deserialize_ms", float(ms))


def note_artifact_load(n=1):
    """A save_aot artifact's pre-serialized modules were loaded — the
    artifact IS an AOT cache hit by construction; counted separately so
    hit/miss ratios stay honest."""
    _bump("artifact_loads", n)


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def fingerprint_key(fingerprint):
    """Canonical content address of a fingerprint dict."""
    blob = json.dumps(fingerprint, sort_keys=True,
                      separators=(",", ":")).encode()
    return hashlib.sha256(blob).hexdigest()


def program_fingerprint(program):
    """Stable content hash of a Program: the sha256 of its canonical
    serialization (framework.Program.serialize_to_string), which covers
    blocks, ops, attrs, var shapes/dtypes, seeds, and uids — two
    identically-built (or identically-loaded) programs in different
    processes hash identically, which is what makes cross-process reuse
    work."""
    return hashlib.sha256(
        program.serialize_to_string().encode()).hexdigest()


def environment_fingerprint(device=None):
    """The reuse-safety fields outside the program: jax + library
    versions and the target device KIND (an executable compiled for one
    TPU generation must never be handed to another; replicas of the
    same kind share one entry)."""
    import jax
    from . import __version__ as lib_version
    if device is None:
        devs = jax.devices()
        device = devs[0] if devs else None
    return {
        "jax": jax.__version__,
        "lib": lib_version,
        "platform": getattr(device, "platform", jax.default_backend()),
        "device_kind": str(getattr(device, "device_kind", "")),
    }


def _spec_sig(arrays):
    """Sorted (name, shape, dtype) signature of a dict of arrays —
    the dtype set + shape bucket part of a fingerprint."""
    return [[n, list(getattr(arrays[n], "shape", ())),
             str(arrays[n].dtype)] for n in sorted(arrays)]


# ---------------------------------------------------------------------------
# the content-addressed AOT store
# ---------------------------------------------------------------------------

def ensure_jax_cache():
    """Place jax's persistent compilation cache and return the directory
    in use.  Executor, ParallelExecutor and CompileCache call this when
    they are built — before their first compile.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and nothing
    here touches jax's config.  Unset: THE one setter of
    ``jax_compilation_cache_dir`` points it at ``<checkout>/.cache/jax``
    — once, since a directory already configured (by an earlier call or
    by the embedding script) stands.  jax initialises its cache lazily
    at the first compile that finds a directory configured, so no reset
    of an already-running process is needed."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir",
                          checkout_cache_dir("jax"))
    return jax.config.jax_compilation_cache_dir


class CompileCache:
    """One store root: get/put of serialized AOT executables by
    fingerprint, with the vault commit discipline and LRU eviction."""

    def __init__(self, root=None, max_mb=None, xla_cache=True):
        from .flags import FLAGS
        self.root = os.path.abspath(root) if root else cache_root()
        self.max_bytes = int(
            (FLAGS.compile_cache_max_mb if max_mb is None else max_mb)
            * (1 << 20))
        self._lock = threading.Lock()
        if xla_cache:
            ensure_jax_cache()

    # -- layout ---------------------------------------------------------

    @property
    def aot_dir(self):
        return os.path.join(self.root, AOT_SUBDIR)

    def entry_dir(self, key):
        return os.path.join(self.aot_dir, key)

    def entries(self):
        """[(key, abs_path)] of committed entries (have a manifest)."""
        if not os.path.isdir(self.aot_dir):
            return []
        out = []
        for name in sorted(os.listdir(self.aot_dir)):
            path = os.path.join(self.aot_dir, name)
            if not name.startswith(_TMP_PREFIX) and os.path.isdir(path) \
                    and os.path.exists(os.path.join(path, MANIFEST_NAME)):
                out.append((name, path))
        return out

    def stale_tmp_dirs(self):
        if not os.path.isdir(self.aot_dir):
            return []
        return [os.path.join(self.aot_dir, n)
                for n in sorted(os.listdir(self.aot_dir))
                if n.startswith(_TMP_PREFIX)]

    # -- read path ------------------------------------------------------

    def get(self, fingerprint):
        """Serialized executable bytes for `fingerprint`, or None.
        Every failure mode — missing entry, unparsable manifest, CRC
        mismatch, truncated blob — is a MISS (the bad entry is
        quarantined), never an exception: corruption must cost a
        recompile, not a crash."""
        key = fingerprint_key(fingerprint)
        d = self.entry_dir(key)
        mpath = os.path.join(d, MANIFEST_NAME)
        try:
            with open(mpath) as f:
                manifest = json.load(f)
            if manifest.get("schema") != SCHEMA_VERSION:
                raise ValueError("schema %r" % manifest.get("schema"))
            with open(os.path.join(d, manifest["file"]), "rb") as f:
                blob = f.read()
            if (binascii.crc32(blob) & 0xFFFFFFFF) != manifest["crc32"] \
                    or len(blob) != manifest["nbytes"]:
                raise ValueError("crc/size mismatch")
        except FileNotFoundError:
            _bump("misses")
            return None
        except Exception:
            # corrupt entry: quarantine and recompile silently
            _bump("errors")
            _bump("misses")
            shutil.rmtree(d, ignore_errors=True)
            return None
        try:
            os.utime(mpath)  # LRU touch
        except OSError:
            pass
        _bump("hits")
        return blob

    # -- write path -----------------------------------------------------

    def put(self, fingerprint, blob):
        """Commit `blob` under the fingerprint's content address with
        the write-temp -> fsync -> rename discipline.  Returns the
        committed entry dir (or the already-committed one if another
        process won the race).  Never raises on IO failure — a cache
        that cannot write degrades to compiling every boot."""
        ckpt = _ckpt()
        key = fingerprint_key(fingerprint)
        final = self.entry_dir(key)
        try:
            os.makedirs(self.aot_dir, exist_ok=True)
            tmp = os.path.join(self.aot_dir, "%s%s.%d.%x" % (
                _TMP_PREFIX, key, os.getpid(), threading.get_ident()))
            self._sweep_tmp(key, keep=tmp)
            os.makedirs(tmp)
            with open(os.path.join(tmp, EXEC_NAME), "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            manifest = {
                "schema": SCHEMA_VERSION,
                "fingerprint": fingerprint,
                "file": EXEC_NAME,
                "crc32": binascii.crc32(blob) & 0xFFFFFFFF,
                "nbytes": len(blob),
                "created": time.time(),
            }
            with open(os.path.join(tmp, MANIFEST_NAME), "w") as f:
                json.dump(manifest, f, indent=1, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            ckpt._fsync_dir(tmp)
            ckpt._chaos("cc_exec_written")
            if os.path.isdir(final):
                # another process committed this fingerprint first; its
                # entry is byte-equivalent by construction — keep it
                shutil.rmtree(tmp, ignore_errors=True)
            else:
                os.rename(tmp, final)
            ckpt._chaos("cc_committed")
            ckpt._fsync_dir(self.aot_dir)
            _bump("puts")
            self._evict(protect=key)
            return final
        except OSError:
            _bump("errors")
            return None

    def _sweep_tmp(self, key=None, keep=None):
        """Remove stale in-flight dirs: any tmp for the SAME key (we are
        about to supersede it — this is the crash repair), plus tmps old
        enough that no live writer can still own them.  Young tmps of
        OTHER keys belong to concurrent processes and are left alone."""
        now = time.time()
        for path in self.stale_tmp_dirs():
            if path == keep:
                continue
            name = os.path.basename(path)[len(_TMP_PREFIX):]
            same_key = key is not None and name.startswith(key + ".")
            try:
                old = (now - os.path.getmtime(path)) > 3600.0
            except OSError:
                old = False
            if same_key or old:
                shutil.rmtree(path, ignore_errors=True)

    # -- eviction -------------------------------------------------------

    def usage_bytes(self):
        total = 0
        for _, d in self.entries():
            for n in os.listdir(d):
                try:
                    total += os.path.getsize(os.path.join(d, n))
                except OSError:
                    pass
        return total

    def _evict(self, protect=None):
        """Size-capped LRU over the aot entries; the `protect` key (the
        entry just written) is never the victim."""
        try:
            victims = []  # (last_used, nbytes, path)
            total = 0
            for key, d in self.entries():
                size = sum(os.path.getsize(os.path.join(d, n))
                           for n in os.listdir(d))
                total += size
                if key != protect:
                    victims.append(
                        (os.path.getmtime(os.path.join(d, MANIFEST_NAME)),
                         size, d))
            if total <= self.max_bytes:
                return
            victims.sort()
            for _, size, path in victims:
                if total <= self.max_bytes:
                    break
                shutil.rmtree(path, ignore_errors=True)
                total -= size
                _bump("evictions")
        except OSError:
            pass  # eviction is advisory; never fail a put over it

    # -- verification (tools/verify_compile_cache.py) -------------------

    def verify(self):
        """[(key, error-or-None, manifest-or-None)] over every committed
        entry — the walk the CLI renders; an error string names exactly
        what is corrupt."""
        out = []
        for key, d in self.entries():
            try:
                with open(os.path.join(d, MANIFEST_NAME)) as f:
                    manifest = json.load(f)
                if manifest.get("schema") != SCHEMA_VERSION:
                    raise ValueError(
                        "manifest schema %r (this build reads %d)"
                        % (manifest.get("schema"), SCHEMA_VERSION))
                fname = manifest["file"]
                with open(os.path.join(d, fname), "rb") as f:
                    blob = f.read()
                crc = binascii.crc32(blob) & 0xFFFFFFFF
                if crc != manifest["crc32"]:
                    raise ValueError(
                        "%s failed CRC32 (manifest %08x != file %08x)"
                        % (fname, manifest["crc32"], crc))
                if len(blob) != manifest["nbytes"]:
                    raise ValueError(
                        "%s truncated (%d bytes, manifest says %d)"
                        % (fname, len(blob), manifest["nbytes"]))
                want = fingerprint_key(manifest.get("fingerprint", {}))
                if want != key:
                    raise ValueError(
                        "fingerprint hashes to %s but entry dir is %s"
                        % (want[:16], key[:16]))
                out.append((key, None, manifest))
            except Exception as e:
                out.append((key, str(e), None))
        return out


_default_cache = None
_default_cache_key = None
_default_lock = threading.Lock()


def default_cache():
    """The process's shared CompileCache for the flag-configured root,
    or None when FLAGS.compile_cache is off.  Re-resolved when the
    flags change (tests repoint compile_cache_dir freely)."""
    global _default_cache, _default_cache_key
    if not cache_enabled():
        return None
    from .flags import FLAGS
    key = (cache_root(), FLAGS.compile_cache_max_mb)
    with _default_lock:
        if _default_cache is None or _default_cache_key != key:
            _default_cache = CompileCache(root=key[0], max_mb=key[1])
            _default_cache_key = key
        return _default_cache


def verify_store(root=None):
    """Walk the store at `root` (default: the flag-configured one) —
    the library half of tools/verify_compile_cache.py."""
    return CompileCache(root=root, xla_cache=False).verify()


# ---------------------------------------------------------------------------
# the repo-wide kernel-tuning registry
# ---------------------------------------------------------------------------
#
# Generalizes ops/attention_tuning.py's shape->config JSON: one file per
# kernel family under <root>/tuning/, the same atomic commit discipline
# as every other write in the store, and the same mtime-memo so a tuner
# in another process shows up without a restart.  attention_tuning now
# reads/writes namespace "flash_attention" here (its legacy JSON stays a
# read-only fallback); future kernels (fused bottleneck blocks, dequant
# matmuls) add namespaces, not new cache formats.

_json_memo = {}  # path -> (mtime, entries)
_json_memo_lock = threading.Lock()


def tuning_path(namespace):
    if not namespace or "/" in namespace or namespace.startswith("."):
        raise ValueError("bad tuning namespace %r" % (namespace,))
    return os.path.join(cache_root(), TUNING_SUBDIR, namespace + ".json")


def _load_json(path):
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {}
    with _json_memo_lock:
        hit = _json_memo.get(path)
        if hit is not None and hit[0] == mtime:
            return hit[1]
    try:
        with open(path) as f:
            raw = json.load(f)
        entries = raw.get("configs", raw) if isinstance(raw, dict) else {}
    except (OSError, ValueError):
        entries = {}  # truncated/corrupt registry reads as empty, never raises
    with _json_memo_lock:
        _json_memo[path] = (mtime, entries)
    return entries


def tuning_entries(namespace):
    """All records in a namespace (dict copy; {} when none)."""
    return dict(_load_json(tuning_path(namespace)))


def tuning_lookup(namespace, key):
    """One record (a plain dict) or None."""
    rec = _load_json(tuning_path(namespace)).get(key)
    return rec if isinstance(rec, dict) else None


def tuning_record(namespace, key, record):
    """Read-modify-write one record with the shared write-temp -> fsync
    -> rename helper (chaos point `tuning_tmp_written` between the
    durable temp and the rename — a killed tuner leaves the previous
    registry intact, never a truncated file)."""
    ckpt = _ckpt()
    path = tuning_path(namespace)
    entries = dict(_load_json(path))
    entries[key] = dict(record)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    payload = {"schema": SCHEMA_VERSION, "namespace": namespace,
               "configs": entries}
    ckpt.atomic_write(
        path, json.dumps(payload, indent=2, sort_keys=True).encode(),
        chaos_point="tuning_tmp_written")
    with _json_memo_lock:
        _json_memo.pop(path, None)
    return path
