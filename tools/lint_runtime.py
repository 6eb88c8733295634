"""Runtime concurrency lint — AST checks for this repo's empirically-
observed threading/durability hazard classes.

    python tools/lint_runtime.py [--smoke] [--show-suppressed] [files...]

Each check encodes a bug class a previous PR shipped and only found at
runtime; the lint catches the pattern mechanically, before it runs:

  notify-shared-cv         `.notify()` on a threading.Condition that has
      waiters in MULTIPLE methods of the class.  One notify wakes an
      arbitrary waiter class and leaves the others sleeping their poll
      interval — PR 7's queue_wait spans exposed exactly this in
      DynamicBatcher.submit (router + lane workers on one cv): a ~100 ms
      idle-latency floor.  Use notify_all on a shared condition.

  nonatomic-vault-write    `open(path, "w"/"wb")` in a vault/store
      module whose enclosing function never commits via
      os.replace/os.rename/atomic_write.  A writer killed mid-write
      leaves a TRUNCATED file where readers expect a committed one —
      PR 6 found attention_tuning.record() rewriting its JSON in place;
      fluid/checkpoint.py `atomic_write` (write-temp -> fsync -> rename)
      is the sanctioned discipline (CHECKPOINT.md).

  nonmonotonic-time        `time.time()` in span/deadline modules.
      Wall clock steps under NTP correction; a duration or deadline
      computed from it can go negative or expire early.  Durations and
      deadlines use time.monotonic(); wall stamps are only for record
      timestamps (the suppression list names each sanctioned site).

  unlocked-shared-mutation  in serving/, a self attribute that is
      mutated under the class's lock in one method and WITHOUT it in
      another.  State that is sometimes protected must always be
      protected — PR 5's double-compile race (Predictor._compiled
      written by concurrent lanes) and PR 6's tuning-record rewrite are
      this class.

  nested-lock-order        two of a class's locks acquired NESTED in
      opposite orders across methods (A then B in one, B then A in
      another).  Two threads taking the two paths concurrently can each
      hold one lock and wait forever on the other — the classic
      lock-order deadlock, and exactly the hazard shape the registry's
      routing-lock + batcher-lane-lock layering must never grow.  Fix:
      one canonical acquisition order (or release the outer lock before
      taking the inner).

Scope: with no file arguments the lint walks paddle_tpu/ and applies
each check to its hazard-relevant modules (vault modules for the write
check, span/deadline modules for the clock check, serving/ for the lock
check).  Explicit file arguments get ALL checks unconditionally — that
is the seeded-defect-fixture mode tests/test_analysis.py pins.

Suppressions: the table below names every sanctioned occurrence as
(path, check, ClassName.method) WITH justification.  An entry that no
longer matches anything fails the run (exit 3) so the table cannot rot.

Exit codes: 0 clean, 2 findings (file:line each), 3 stale suppression,
1 usage error.
"""

import argparse
import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# check scoping (repo mode)
# ---------------------------------------------------------------------------

# modules participating in a vault/store commit protocol: raw writes
# here must ride the atomic_write discipline
VAULT_MODULES = (
    "paddle_tpu/fluid/checkpoint.py",
    "paddle_tpu/compile_cache.py",
    "paddle_tpu/distributed/elastic.py",
    "paddle_tpu/obs/events.py",
    "paddle_tpu/obs/flightrec.py",
    "paddle_tpu/ops/attention_tuning.py",
)

# modules computing spans/deadlines: durations here must be monotonic
TIME_MODULES = (
    "paddle_tpu/serving/",
    "paddle_tpu/obs/",
    "paddle_tpu/fluid/pipeline.py",
    "paddle_tpu/utils/retry.py",
    "paddle_tpu/reader/decorator.py",
    "paddle_tpu/inference/decode.py",
)

# modules whose classes serve concurrent threads: the lock-consistency
# check applies
LOCK_MODULES = (
    "paddle_tpu/serving/",
    "paddle_tpu/obs/",
    "paddle_tpu/compile_cache.py",
)

# the notify check is cheap and precise — repo-wide
NOTIFY_MODULES = ("paddle_tpu/",)

# ---------------------------------------------------------------------------
# suppressions — every entry is a sanctioned occurrence WITH its reason.
# Keyed (relpath, check, symbol): symbol is Class.method (or module-level
# function name).  A stale entry (matching nothing) fails the run.
# ---------------------------------------------------------------------------

SUPPRESSIONS = [
    ("paddle_tpu/obs/tracing.py", "nonmonotonic-time", "<module>",
     "the ONE wall-clock reading of the tracer: the process-wide "
     "wall - monotonic offset every span's record `ts` is derived "
     "from; starts and durations ride time.monotonic() alone, and no "
     "emitter reads the wall clock"),
    ("paddle_tpu/obs/events.py", "nonmonotonic-time", "EventLog.emit",
     "event `ts` is the wall-clock record timestamp operators grep "
     "against log files; no duration is derived from it"),
    ("paddle_tpu/obs/slo.py", "nonmonotonic-time",
     "SLOMonitor._read_lane",
     "sample `ts` is the wall-clock RECORD stamp the timeline/bundle "
     "files carry for operators; every interval/age computation rides "
     "the sample's separate monotonic `mono` field"),
    ("paddle_tpu/obs/flightrec.py", "nonmonotonic-time",
     "FlightRecorder.dump",
     "manifest `ts` is the wall-clock record stamp operators correlate "
     "bundles with logs by; cooldown and dump_ms durations ride "
     "time.monotonic()"),
]


class Finding:
    __slots__ = ("path", "line", "check", "symbol", "message",
                 "suppressed")

    def __init__(self, path, line, check, symbol, message):
        self.path = path
        self.line = line
        self.check = check
        self.symbol = symbol
        self.message = message
        self.suppressed = False

    def __str__(self):
        return "%s:%d: [%s] %s (%s)" % (self.path, self.line, self.check,
                                        self.message, self.symbol)


# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------

def _is_self_attr(node, attr=None):
    return (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
            and (attr is None or node.attr == attr))


def _call_name(call):
    """'threading.Condition' / 'Condition' / 'os.replace' ... for a Call."""
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    parts = []
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
    return ".".join(reversed(parts))


_LOCK_FACTORIES = ("Lock", "RLock", "Condition")
_MUTATING_METHODS = frozenset([
    "append", "extend", "insert", "pop", "popleft", "appendleft",
    "remove", "clear", "update", "add", "discard", "setdefault",
])


def _lock_attrs_of_class(cls):
    """self attrs assigned a threading.Lock/RLock/Condition anywhere in
    the class; conditions separately (they are locks too)."""
    locks, conds = set(), set()
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and isinstance(node.value,
                                                       ast.Call):
            name = _call_name(node.value)
            base = name.rsplit(".", 1)[-1]
            if base in _LOCK_FACTORIES:
                for t in node.targets:
                    if _is_self_attr(t):
                        locks.add(t.attr)
                        if base == "Condition":
                            conds.add(t.attr)
    return locks, conds


def _method_iter(cls):
    for node in cls.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


class _MethodScan(ast.NodeVisitor):
    """One method: wait/notify calls on self-attr conditions, and self
    attribute mutations, each tagged with whether a `with self.<lock>`
    lexically encloses it."""

    def __init__(self, lock_attrs):
        self.lock_attrs = lock_attrs
        self.depth = 0
        self.waits = []        # (cond_attr, line)
        self.notifies = []     # (cond_attr, line, is_notify_all)
        self.mutations = []    # (attr, line, under_lock, desc)

    def visit_With(self, node):
        locked = any(
            _is_self_attr(item.context_expr)
            and item.context_expr.attr in self.lock_attrs
            for item in node.items)
        if locked:
            self.depth += 1
        self.generic_visit(node)
        if locked:
            self.depth -= 1

    def _note_mut(self, target, line, desc):
        # self.x = / self.x[k] = / self.x += ...
        t = target
        if isinstance(t, ast.Subscript):
            t = t.value
            desc += "[...]"
        if _is_self_attr(t):
            self.mutations.append((t.attr, line, self.depth > 0, desc))

    def visit_Assign(self, node):
        for t in node.targets:
            self._note_mut(t, node.lineno, "assignment to self.%s"
                           % _attr_of(t))
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._note_mut(node.target, node.lineno,
                       "augmented assignment to self.%s"
                       % _attr_of(node.target))
        self.generic_visit(node)

    def visit_Delete(self, node):
        for t in node.targets:
            self._note_mut(t, node.lineno, "del on self.%s" % _attr_of(t))
        self.generic_visit(node)

    def visit_Call(self, node):
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr in ("wait", "notify", "notify_all") and \
                    _is_self_attr(f.value):
                if f.attr == "wait":
                    self.waits.append((f.value.attr, node.lineno))
                else:
                    self.notifies.append((f.value.attr, node.lineno,
                                          f.attr == "notify_all"))
            elif f.attr in _MUTATING_METHODS and _is_self_attr(f.value):
                self.mutations.append(
                    (f.value.attr, node.lineno, self.depth > 0,
                     "self.%s.%s()" % (f.value.attr, f.attr)))
        self.generic_visit(node)


def _attr_of(node):
    t = node
    if isinstance(t, ast.Subscript):
        t = t.value
    return t.attr if isinstance(t, ast.Attribute) else "?"


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def check_notify_shared_cv(relpath, tree, findings):
    for cls in [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]:
        lock_attrs, cond_attrs = _lock_attrs_of_class(cls)
        if not cond_attrs:
            continue
        waiters = {}    # cond attr -> set of method names that wait
        notifies = []   # (cond, method, line, is_all)
        for m in _method_iter(cls):
            scan = _MethodScan(lock_attrs)
            scan.visit(m)
            for cond, _line in scan.waits:
                if cond in cond_attrs:
                    waiters.setdefault(cond, set()).add(m.name)
            for cond, line, is_all in scan.notifies:
                if cond in cond_attrs:
                    notifies.append((cond, m.name, line, is_all))
        for cond, method, line, is_all in notifies:
            if is_all:
                continue
            if len(waiters.get(cond, ())) >= 2:
                findings.append(Finding(
                    relpath, line, "notify-shared-cv",
                    "%s.%s" % (cls.name, method),
                    "notify() on self.%s, which has waiters in %d "
                    "methods (%s) — one notify wakes an arbitrary "
                    "waiter class and leaves the others polling; use "
                    "notify_all()" % (cond, len(waiters[cond]),
                                      ", ".join(sorted(waiters[cond])))))


def _write_mode(call):
    """'w'/'wb' if this is open(..., w-mode), else None."""
    if _call_name(call) not in ("open", "io.open"):
        return None
    mode = None
    if len(call.args) >= 2 and isinstance(call.args[1], ast.Constant):
        mode = call.args[1].value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
            mode = kw.value.value
    if isinstance(mode, str) and "w" in mode:
        return mode
    return None


def check_vault_write(relpath, tree, findings):
    # enclosing function -> does it (or the module) commit atomically?
    commit_calls = ("os.replace", "replace", "os.rename", "rename",
                    "atomic_write", "_atomic_write")

    def scan_scope(scope, symbol):
        # ast.walk descends into nested defs too: a commit anywhere in
        # the function (or its closures) sanctions the writes in it —
        # the discipline is "commit near the write", not lexical nesting
        commits = False
        opens = []
        for node in ast.walk(scope):
            if isinstance(node, ast.Call):
                if _call_name(node) in commit_calls:
                    commits = True
                m = _write_mode(node)
                if m is not None:
                    opens.append((node.lineno, m))
        for line, m in opens:
            if not commits:
                findings.append(Finding(
                    relpath, line, "nonatomic-vault-write", symbol,
                    "open(..., %r) in a vault/store module with no "
                    "os.replace/atomic_write commit in scope — a "
                    "writer killed mid-write leaves a truncated file "
                    "where readers expect a committed one; use "
                    "fluid.checkpoint.atomic_write" % m))

    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan_scope(node, node.name)
        elif isinstance(node, ast.ClassDef):
            for m in _method_iter(node):
                scan_scope(m, "%s.%s" % (node.name, m.name))


def check_wallclock(relpath, tree, findings):
    # time.time() (or _time.time()) calls, attributed to Class.method
    def scan(scope, symbol):
        for node in ast.iter_child_nodes(scope):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scan(node, (symbol + "." + node.name)
                     if symbol else node.name)
            elif isinstance(node, ast.ClassDef):
                scan(node, (symbol + "." + node.name)
                     if symbol else node.name)
            else:
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call) and \
                            isinstance(sub.func, ast.Attribute) and \
                            sub.func.attr == "time" and \
                            isinstance(sub.func.value, ast.Name) and \
                            sub.func.value.id in ("time", "_time"):
                        findings.append(Finding(
                            relpath, sub.lineno, "nonmonotonic-time",
                            symbol or "<module>",
                            "time.time() in a span/deadline module — "
                            "wall clock steps under NTP; durations and "
                            "deadlines must use time.monotonic() "
                            "(wall stamps for record fields need a "
                            "suppression naming why)"))

    scan(tree, "")


class _LockOrderScan(ast.NodeVisitor):
    """One method: ordered (outer, inner, line) acquisition pairs of
    the class's self-attr locks — both nested ``with self._a:`` /
    ``with self._b:`` blocks and multi-item ``with self._a, self._b:``
    statements count, in lexical order."""

    def __init__(self, lock_attrs):
        self.lock_attrs = lock_attrs
        self.held = []          # acquisition stack of lock attr names
        self.pairs = []         # (outer, inner, line)

    def visit_With(self, node):
        acquired = []
        for item in node.items:
            ce = item.context_expr
            if _is_self_attr(ce) and ce.attr in self.lock_attrs:
                for outer in self.held + acquired:
                    self.pairs.append((outer, ce.attr, node.lineno))
                acquired.append(ce.attr)
        self.held.extend(acquired)
        self.generic_visit(node)
        del self.held[len(self.held) - len(acquired):]


def check_lock_order(relpath, tree, findings):
    for cls in [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]:
        lock_attrs, _conds = _lock_attrs_of_class(cls)
        if len(lock_attrs) < 2:
            continue
        order = {}     # (outer, inner) -> (method, line) first site
        for m in _method_iter(cls):
            scan = _LockOrderScan(lock_attrs)
            scan.visit(m)
            for outer, inner, line in scan.pairs:
                if outer != inner:
                    order.setdefault((outer, inner), (m.name, line))
        for (a, b), (meth, line) in sorted(order.items()):
            if a > b:
                continue          # report each unordered pair once
            rev = order.get((b, a))
            if rev is None:
                continue
            findings.append(Finding(
                relpath, line, "nested-lock-order",
                "%s.%s" % (cls.name, meth),
                "self.%s is taken inside self.%s here, but %s (line "
                "%d) nests them the other way around — two threads on "
                "the two paths can each hold one lock and wait forever "
                "on the other; pick one canonical order" % (
                    b, a, rev[0], rev[1])))


def check_unlocked_mutation(relpath, tree, findings):
    for cls in [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]:
        lock_attrs, _conds = _lock_attrs_of_class(cls)
        if not lock_attrs:
            continue
        locked_attrs = set()     # attrs mutated under a lock somewhere
        sites = []               # (attr, line, under, method, desc)
        for m in _method_iter(cls):
            scan = _MethodScan(lock_attrs)
            scan.visit(m)
            # a method named *_locked runs with the caller holding the
            # lock (the repo's convention, e.g. EventLog._rotate_locked)
            held = m.name.endswith("_locked")
            for attr, line, under, desc in scan.mutations:
                if attr in lock_attrs:
                    continue
                under = under or held
                if m.name != "__init__":
                    sites.append((attr, line, under, m.name, desc))
                if under:
                    locked_attrs.add(attr)
        for attr, line, under, method, desc in sites:
            if attr in locked_attrs and not under:
                findings.append(Finding(
                    relpath, line, "unlocked-shared-mutation",
                    "%s.%s" % (cls.name, method),
                    "%s without the lock, but %s protects the same "
                    "attribute with its lock elsewhere — sometimes-"
                    "locked state must be always-locked (or earn a "
                    "suppression naming why this site is safe)"
                    % (desc, cls.name)))


CHECKS = (
    ("notify-shared-cv", NOTIFY_MODULES, check_notify_shared_cv),
    ("nonatomic-vault-write", VAULT_MODULES, check_vault_write),
    ("nonmonotonic-time", TIME_MODULES, check_wallclock),
    ("unlocked-shared-mutation", LOCK_MODULES, check_unlocked_mutation),
    # the deadlock-shape check is cheap and precise — repo-wide, like
    # the notify check
    ("nested-lock-order", NOTIFY_MODULES, check_lock_order),
)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _iter_repo_files(root):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def lint_files(paths, all_checks=False, repo_root=REPO):
    findings = []
    for path in paths:
        relpath = os.path.relpath(path, repo_root).replace(os.sep, "/")
        try:
            with open(path, "r") as f:
                tree = ast.parse(f.read(), filename=path)
        except SyntaxError as e:
            findings.append(Finding(relpath, e.lineno or 0, "parse-error",
                                    "<module>", str(e)))
            continue
        for check_name, modules, fn in CHECKS:
            if all_checks or any(relpath.startswith(m) for m in modules):
                fn(relpath, tree, findings)
    return findings


def apply_suppressions(findings):
    """Mark suppressed findings; return the list of STALE suppression
    entries (matching nothing — the table must not rot)."""
    used = [False] * len(SUPPRESSIONS)
    for f in findings:
        for i, (path, check, symbol, _why) in enumerate(SUPPRESSIONS):
            if f.path == path and f.check == check and f.symbol == symbol:
                f.suppressed = True
                used[i] = True
    return [SUPPRESSIONS[i] for i, u in enumerate(used) if not u]


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="concurrency/durability lint over paddle_tpu/")
    ap.add_argument("files", nargs="*",
                    help="explicit files: ALL checks apply (fixture "
                         "mode); default walks paddle_tpu/ with "
                         "per-check module scoping")
    ap.add_argument("--smoke", action="store_true",
                    help="summary only (the tier-1 CI mode)")
    ap.add_argument("--show-suppressed", action="store_true")
    args = ap.parse_args(argv)

    if args.files:
        findings = lint_files([os.path.abspath(f) for f in args.files],
                              all_checks=True,
                              repo_root=os.getcwd())
        stale = []
    else:
        root = os.path.join(REPO, "paddle_tpu")
        findings = lint_files(list(_iter_repo_files(root)))
        stale = apply_suppressions(findings)

    live = [f for f in findings if not f.suppressed]
    n_sup = len(findings) - len(live)
    for f in live:
        print(f)
    if args.show_suppressed:
        for f in findings:
            if f.suppressed:
                print("suppressed: %s" % f)
    if stale:
        for s in stale:
            print("STALE suppression (matches nothing): %s" % (s[:3],))
        print("lint_runtime: FAIL (%d stale suppression entries)"
              % len(stale))
        return 3
    if live:
        print("lint_runtime: FAIL (%d finding(s), %d suppressed)"
              % (len(live), n_sup))
        return 2
    print("lint_runtime: OK (%d file(s), %d finding(s) suppressed "
          "by the justified table)"
          % (len(args.files) if args.files else
             sum(1 for _ in _iter_repo_files(
                 os.path.join(REPO, "paddle_tpu"))), n_sup))
    return 0


if __name__ == "__main__":
    sys.exit(main())
