"""graftsync (lint/flow concurrency + crash-consistency tier) tests —
ISSUE 16 tentpole.

Same stance as test_lint.py / test_lint_flow.py: every rule is proven to
FIRE on a seeded violation and to stay QUIET on the shipped tree; each
rule additionally gets a MUTATION test against the real service sources
(demote a guarded access out of its ``with``, move a compact() call
inside the journal lock, drop an fsync, drop the atomic-replace publish,
revert a knob parse to raw int()) — a checker that cannot catch the
regression it was built for is indistinguishable from one that does not
run. Plus lock-region CFG fixtures (try/finally, early return,
exception paths), pragma load-bearing checks, the env_str/env_float
knob-parsing regressions the envknobs findings were fixed with, and the
--knob-registry / SARIF helpUri CLI workflow. Tier-1, CPU-only; the
analyzers import no jax.
"""

import functools
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from jepsen_jgroups_raft_tpu.lint import cli, report
from jepsen_jgroups_raft_tpu.lint.base import SourceFile
from jepsen_jgroups_raft_tpu.lint.flow import (crashproto, envknobs,
                                               guarded, lockorder)
from jepsen_jgroups_raft_tpu.lint.flow.cfg import cfg_for
from jepsen_jgroups_raft_tpu.lint.flow.locks import lock_regions
from jepsen_jgroups_raft_tpu.platform import env_float, env_str

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "jepsen_jgroups_raft_tpu"
SERVICE = PKG / "service"


def rules_of(findings):
    return {f.rule for f in findings}


def src_of(text, path="service/mod.py"):
    return SourceFile.from_text(path, text)


def held_lines(source, func):
    """line -> set of held lock names, unioned over the CFG nodes."""
    g = cfg_for(source, func)
    held = lock_regions(g)
    out = {}
    for n in g.nodes:
        if n.line is not None:
            out.setdefault(n.line, set()).update(held[n.idx])
    return out


# ------------------------------------------------------- lock regions


class TestLockRegions:
    def test_with_lock_region_covers_body_not_tail(self):
        h = held_lines(
            "def f(self):\n"
            "    with self._lock:\n"
            "        touch(self)\n"      # line 3
            "    after(self)\n", "f")    # line 4
        assert "self._lock" in h[3]
        assert "self._lock" not in h[4]

    def test_try_finally_inside_with_stays_held(self):
        h = held_lines(
            "def f(self):\n"
            "    with self._lock:\n"
            "        try:\n"
            "            risky(self)\n"       # line 4
            "        finally:\n"
            "            cleanup(self)\n"     # line 6
            "    after(self)\n", "f")         # line 7
        assert "self._lock" in h[4]
        assert "self._lock" in h[6]
        assert "self._lock" not in h[7]

    def test_early_return_does_not_leak_region(self):
        h = held_lines(
            "def f(self):\n"
            "    with self._lock:\n"
            "        if self.done:\n"
            "            return None\n"
            "        work(self)\n"        # line 5
            "    after(self)\n", "f")     # line 6
        assert "self._lock" in h[5]
        assert "self._lock" not in h[6]

    def test_exception_path_ends_region_at_exit_marker(self):
        # the handler runs AFTER __exit__ released the lock
        h = held_lines(
            "def f(self):\n"
            "    try:\n"
            "        with self._lock:\n"
            "            risky(self)\n"       # line 4
            "    except ValueError:\n"
            "        handle(self)\n", "f")    # line 6
        assert "self._lock" in h[4]
        assert "self._lock" not in h[6]

    def test_nested_locks_accumulate(self):
        h = held_lines(
            "def f(self):\n"
            "    with self._lock:\n"
            "        with self._gcond:\n"
            "            both(self)\n"        # line 4
            "        one(self)\n", "f")       # line 5
        assert {"self._lock", "self._gcond"} <= h[4]
        assert "self._gcond" not in h[5]


# ------------------------------------------------------------ guarded


GUARDED_FIXTURE = (
    "import threading\n"
    "\n"
    "class Reg:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._entries = {{}}  # guarded_by(_lock)\n"
    "\n"
    "    def touch(self):\n"
    "{body}")


class TestGuarded:
    def test_unguarded_write_fires(self):
        f = guarded.analyze_source(src_of(GUARDED_FIXTURE.format(
            body="        self._entries['k'] = 1\n")))
        assert rules_of(f) == {guarded.RULE}

    def test_with_lock_is_quiet(self):
        f = guarded.analyze_source(src_of(GUARDED_FIXTURE.format(
            body="        with self._lock:\n"
                 "            self._entries['k'] = 1\n")))
        assert not f

    def test_requires_comment_satisfies(self):
        text = GUARDED_FIXTURE.format(
            body="        self._entries['k'] = 1\n").replace(
            "def touch(self):", "def touch(self):  # requires(_lock)")
        assert not guarded.analyze_source(src_of(text))

    def test_pragma_is_load_bearing(self):
        text = GUARDED_FIXTURE.format(
            body="        return len(self._entries)"
                 "  # lint: allow(unguarded)\n")
        assert not guarded.analyze_source(src_of(text))
        stripped = text.replace("  # lint: allow(unguarded)", "")
        assert rules_of(guarded.analyze_source(src_of(stripped))) == \
            {guarded.RULE}

    def test_init_is_exempt(self):
        text = (
            "import threading\n"
            "class Reg:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._entries = {}  # guarded_by(_lock)\n"
            "        self._entries['seed'] = 1\n")
        assert not guarded.analyze_source(src_of(text))

    def test_cross_object_access_fires_and_lock_satisfies(self):
        base = (
            "import threading\n"
            "class Reg:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._entries = {{}}  # guarded_by(_lock)\n"
            "def peek(reg):\n"
            "{body}")
        hot = base.format(body="    return reg._entries.get('k')\n")
        assert rules_of(guarded.analyze_source(src_of(hot))) == \
            {guarded.RULE}
        cold = base.format(
            body="    with reg._lock:\n"
                 "        return reg._entries.get('k')\n")
        assert not guarded.analyze_source(src_of(cold))

    def test_shipped_service_tier_clean(self):
        for mod in ("daemon.py", "journal.py", "stream.py",
                    "admission.py", "scheduler.py", "store.py"):
            f = guarded.analyze_file(SERVICE / mod)
            assert not f, (mod, f)

    def test_mutation_demoted_lock_fires_on_real_daemon(self):
        # drop every CheckingService critical section: its annotated
        # registries (_requests, _stats, ...) are now touched bare
        text = (SERVICE / "daemon.py").read_text()
        assert "with self._lock:" in text
        mutated = text.replace("with self._lock:",
                               "if True:  # lock dropped")
        f = guarded.analyze_source(src_of(mutated, "service/daemon.py"))
        assert guarded.RULE in rules_of(f)
        assert len(f) > 3  # a whole tier of registries went bare

    def test_stream_pragmas_are_load_bearing(self):
        text = (SERVICE / "stream.py").read_text()
        assert "# lint: allow(unguarded)" in text
        stripped = text.replace("  # lint: allow(unguarded)", "")
        f = guarded.analyze_source(src_of(stripped, "service/stream.py"))
        assert rules_of(f) == {guarded.RULE}


# ---------------------------------------------------------- lockorder


CYCLE_FIXTURE = (
    "import threading\n"
    "class A:\n"
    "    def __init__(self):\n"
    "        self.a_lock = threading.Lock()\n"
    "        self.peer = B()\n"
    "    def fwd(self):\n"
    "        with self.a_lock:\n"
    "            self.peer.back(self)\n"
    "class B:\n"
    "    def __init__(self):\n"
    "        self.b_lock = threading.Lock()\n"
    "    def back(self, other: 'A'):\n"
    "        with self.b_lock:\n"
    "            other.poke()\n")


class TestLockOrder:
    def test_two_lock_cycle_fires(self):
        text = CYCLE_FIXTURE.replace(
            "            other.poke()\n",
            "            with other.a_lock:\n"
            "                pass\n")
        f = lockorder.analyze_sources(
            {"mod.py": src_of(text, "service/mod.py")},
            hierarchy=None)
        assert lockorder.RULE_CYCLE in rules_of(f)

    def test_consistent_order_is_quiet(self):
        f = lockorder.analyze_sources(
            {"mod.py": src_of(CYCLE_FIXTURE, "service/mod.py")},
            hierarchy=["A.a_lock", "B.b_lock"])
        assert not f

    def test_inverted_hierarchy_pair_fires_order(self):
        # the code acquires a_lock -> b_lock; pin the OPPOSITE order
        f = lockorder.analyze_sources(
            {"mod.py": src_of(CYCLE_FIXTURE, "service/mod.py")},
            hierarchy=["B.b_lock", "A.a_lock"])
        assert lockorder.RULE_ORDER in rules_of(f)

    def test_declared_but_unranked_lock_fires_rank(self):
        f = lockorder.analyze_sources(
            {"mod.py": src_of(CYCLE_FIXTURE, "service/mod.py")},
            hierarchy=["A.a_lock"])
        assert lockorder.RULE_RANK in rules_of(f)

    def test_nonreentrant_self_acquire_fires(self):
        text = (
            "import threading\n"
            "class J:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def inner(self):\n"
            "        with self._lock:\n"
            "            pass\n"
            "    def outer(self):\n"
            "        with self._lock:\n"
            "            self.inner()\n")
        f = lockorder.analyze_sources(
            {"mod.py": src_of(text, "service/mod.py")}, hierarchy=None)
        assert lockorder.RULE_CYCLE in rules_of(f)

    def test_rlock_self_acquire_is_quiet(self):
        text = (
            "import threading\n"
            "class J:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.RLock()\n"
            "    def inner(self):\n"
            "        with self._lock:\n"
            "            pass\n"
            "    def outer(self):\n"
            "        with self._lock:\n"
            "            self.inner()\n")
        f = lockorder.analyze_sources(
            {"mod.py": src_of(text, "service/mod.py")}, hierarchy=None)
        assert lockorder.RULE_CYCLE not in rules_of(f)

    def test_shipped_service_tier_clean(self):
        assert not lockorder.analyze_file(SERVICE / "daemon.py")

    def test_every_hierarchy_lock_is_a_real_declaration(self):
        # the pinned order must not drift from the code: each ranked
        # lock (module-qualified or Class.attr) exists in service/
        tier = "".join((SERVICE / m).read_text()
                       for m in os.listdir(SERVICE) if m.endswith(".py"))
        for entry in lockorder.HIERARCHY:
            cls, attr = entry.rsplit(".", 1)
            assert attr in tier, entry
            if not entry.startswith(("store.", "daemon.", "journal.")):
                assert f"class {cls}" in tier, entry

    def test_mutation_compact_inside_journal_lock_fires_cycle(self):
        # the trigger hands the compaction to a thread of the journal's
        # own, outside `with self._lock:`. Mutation: compact there and
        # then, INSIDE the lock — compact() takes the (non-reentrant)
        # lock for its first step, so it is a guaranteed self-deadlock
        text = (SERVICE / "journal.py").read_text()
        before = ("            if wake:\n"
                  "                self._compact_pending = True\n"
                  "        if wake:\n"
                  "            threading.Thread("
                  "target=self._compact_in_background,\n")
        assert before in text
        mutated = text.replace(before, (
            "            if wake:\n"
            "                self.compact()\n"
            "        if False:\n"
            "            threading.Thread("
            "target=self._compact_in_background,\n"))
        f = lockorder.analyze_sources(
            {"journal.py": src_of(mutated, "service/journal.py")},
            hierarchy=None)
        assert lockorder.RULE_CYCLE in rules_of(f)


# --------------------------------------------------------- crashproto


class TestCrashProto:
    def test_missing_fsync_before_return_fires(self):
        text = (
            "import os\n"
            "def append(path, line):\n"
            "    fh = open(path, 'ab')\n"
            "    fh.write(line)\n"
            "    fh.flush()\n"
            "    return True\n")
        f = crashproto.analyze_source(src_of(text))
        assert rules_of(f) == {crashproto.RULE_FSYNC}

    def test_fsync_dominating_return_is_quiet(self):
        text = (
            "import os\n"
            "def append(path, line):\n"
            "    fh = open(path, 'ab')\n"
            "    fh.write(line)\n"
            "    fh.flush()\n"
            "    os.fsync(fh.fileno())\n"
            "    return True\n")
        assert not crashproto.analyze_source(src_of(text))

    def test_fsync_optout_guard_is_quiet(self):
        # the caller opted out of durability on the else arm — that is
        # the journal's documented fsync=False contract, not a bug
        text = (
            "import os\n"
            "def append(path, line, fsync):\n"
            "    fh = open(path, 'ab')\n"
            "    fh.write(line)\n"
            "    if fsync:\n"
            "        os.fsync(fh.fileno())\n"
            "    return True\n")
        assert not crashproto.analyze_source(src_of(text))

    def test_exception_path_is_not_an_ack(self):
        text = (
            "import os\n"
            "def append(path, line):\n"
            "    fh = open(path, 'ab')\n"
            "    fh.write(line)\n"
            "    raise RuntimeError('disk gone')\n")
        assert not crashproto.analyze_source(src_of(text))

    def test_inplace_publish_fires_and_replace_is_quiet(self):
        hot = (
            "import json, os\n"
            "def publish(path, rec):\n"
            "    with open(path, 'w') as fh:\n"
            "        json.dump(rec, fh)\n")
        f = crashproto.analyze_source(src_of(hot))
        assert rules_of(f) == {crashproto.RULE_INPLACE}
        cold = (
            "import json, os\n"
            "def publish(path, tmp, rec):\n"
            "    with open(tmp, 'w') as fh:\n"
            "        json.dump(rec, fh)\n"
            "    os.replace(tmp, path)\n")
        assert not crashproto.analyze_source(src_of(cold))

    def test_append_mode_is_wal_family_not_publish(self):
        text = (
            "import os\n"
            "def log(path, line):\n"
            "    with open(path, 'ab') as fh:\n"
            "        fh.write(line)\n"
            "        os.fsync(fh.fileno())\n")
        assert not crashproto.analyze_source(src_of(text))

    def test_shutil_move_fires_and_pragma_suppresses(self):
        text = (
            "import shutil\n"
            "def adopt(src, dst):\n"
            "    shutil.move(src, dst)\n")
        f = crashproto.analyze_source(src_of(text))
        assert rules_of(f) == {crashproto.RULE_SHUTIL}
        allowed = text.replace(
            "shutil.move(src, dst)",
            "shutil.move(src, dst)  # lint: allow(nonatomic-publish)")
        assert not crashproto.analyze_source(src_of(allowed))

    def test_shipped_service_tier_clean(self):
        for mod in os.listdir(SERVICE):
            if mod.endswith(".py"):
                f = crashproto.analyze_file(SERVICE / mod)
                assert not f, (mod, f)

    def test_mutation_dropped_fsync_fires_on_real_journal(self):
        text = (SERVICE / "journal.py").read_text()
        assert "os.fsync(fh.fileno())" in text
        mutated = text.replace("os.fsync(fh.fileno())", "pass")
        f = crashproto.analyze_source(
            src_of(mutated, "service/journal.py"))
        lines = {x.line for x in f if x.rule == crashproto.RULE_FSYNC}
        # every write site the fsyncs used to dominate: _append,
        # _append_grouped's leader, compact's temp rewrite
        assert len(lines) >= 3, f

    def test_mutation_dropped_replace_fires_on_real_store(self):
        text = (SERVICE / "store.py").read_text()
        assert "os.replace(tmp, path)" in text
        mutated = text.replace("os.replace(tmp, path)",
                               "pass  # publish dropped")
        f = crashproto.analyze_source(src_of(mutated, "service/store.py"))
        assert crashproto.RULE_INPLACE in rules_of(f)

    def test_mutation_daemon_trace_inplace_fires(self):
        # revert the _write_trace atomic publish to in-place writes
        # (both replaces: the rule tracks the temp NAME per function,
        # and _write_trace reuses `tmp` for both files)
        text = (SERVICE / "daemon.py").read_text()
        assert 'os.replace(tmp, d / "results.json")' in text
        mutated = text.replace(
            'os.replace(tmp, d / "results.json")', "pass").replace(
            'os.replace(tmp, d / "history.jsonl")', "pass")
        f = crashproto.analyze_source(src_of(mutated, "service/daemon.py"))
        assert crashproto.RULE_INPLACE in rules_of(f)


# ----------------------------------------------------------- envknobs


class TestEnvKnobs:
    def test_raw_parse_fires(self):
        text = ("import os\n"
                "N = int(os.environ.get('JGRAFT_FOO', '3'))\n")
        f = envknobs.analyze_source(src_of(text, "mod.py"),
                                    doc_names={"JGRAFT_FOO"})
        assert rules_of(f) == {envknobs.RULE_RAW}

    def test_typed_helper_is_quiet(self):
        text = ("from jepsen_jgroups_raft_tpu.platform import env_int\n"
                "N = env_int('JGRAFT_FOO', 3)\n")
        assert not envknobs.analyze_source(src_of(text, "mod.py"),
                                           doc_names={"JGRAFT_FOO"})

    def test_undocumented_knob_fires(self):
        text = ("from jepsen_jgroups_raft_tpu.platform import env_int\n"
                "N = env_int('JGRAFT_FOO', 3)\n")
        f = envknobs.analyze_source(src_of(text, "mod.py"),
                                    doc_names=set())
        assert rules_of(f) == {envknobs.RULE_DOC}

    def test_doc_brace_groups_expand(self):
        names = envknobs.doc_knob_names(
            "| `JGRAFT_STREAM_{IDLE_S,SEGS_PER_S}` | budget |\n")
        assert {"JGRAFT_STREAM_IDLE_S",
                "JGRAFT_STREAM_SEGS_PER_S"} <= names

    def test_registry_harvests_the_repo_clean(self):
        registry, findings = envknobs.build_registry(REPO)
        assert not findings, findings
        knobs = registry["knobs"]
        assert registry["version"] == 2  # PR-17 adds class columns
        # the PR 12-15 knobs the audit reconciled are all present,
        # typed, and documented
        for name in ("JGRAFT_SERVICE_WATCHDOG_S", "JGRAFT_SCAN_CHUNK",
                     "JGRAFT_JOURNAL_GROUP_MS", "JGRAFT_CLUSTER_TTL_S",
                     "JGRAFT_STREAM_IDLE_S"):
            assert name in knobs, name
            assert knobs[name]["documented"], name
            assert knobs[name]["sites"], name
        via = {s["via"] for s in knobs["JGRAFT_SCAN_CHUNK"]["sites"]}
        assert via == {"env_int"}

    def test_mutation_reverted_parse_fires(self):
        text = (SERVICE / "daemon.py").read_text()
        good = 'env_float("JGRAFT_SERVICE_WATCHDOG_S", 30.0, minimum=0.0)'
        assert good in text
        mutated = text.replace(
            good, 'float(os.environ.get("JGRAFT_SERVICE_WATCHDOG_S",'
                  ' "30"))')
        f = envknobs.analyze_source(src_of(mutated, "service/daemon.py"),
                                    doc_names=None)
        raw = [x for x in f if x.rule == envknobs.RULE_RAW]
        assert raw and "JGRAFT_SERVICE_WATCHDOG_S" in raw[0].message


# --------------------------------------- the knob count only falls


#: Distinct ``JGRAFT_*`` names (brace-group prefixes in test strings
#: included) the Python tree holds. A ratchet: lower it with every name
#: a PR deletes; a PR that has to raise it says why in ROADMAP D3.
KNOB_NAMES_MAX = 75
_PY_ROOTS = ("jepsen_jgroups_raft_tpu", "tests", "scripts", "benchmarks",
             "provision")


def test_knob_names_only_fall_and_none_is_a_bench_knob():
    files = [p for r in _PY_ROOTS for p in (REPO / r).rglob("*.py")]
    files += list(REPO.glob("*.py"))
    names = set()
    for p in files:
        names |= set(re.findall(r"JGRAFT_[A-Z0-9_]+", p.read_text()))
    assert not [n for n in names if "BENCH" in n], sorted(names)
    assert len(names) <= KNOB_NAMES_MAX, sorted(names)


# ------------------------------ a document's commands are in the tree


@functools.lru_cache(maxsize=None)
def _tree_py():
    """Every `*.py` of the tree by its path from the root (hidden
    directories and what a chip call brought back apart)."""
    return frozenset(
        str(p) for p in (q.relative_to(REPO) for q in REPO.rglob("*.py"))
        if not any(d.startswith(".") or d == "chiprun_out"
                   for d in p.parts[:-1]))


@pytest.mark.parametrize("doc", [
    "README.md", "doc/running.md", "doc/checker-design.md",
    "doc/intro.md", ".claude/skills/verify/SKILL.md",
    ".github/workflows/lint.yml"])
def test_every_script_a_document_runs_or_names_by_path_exists(doc):
    """Every `python x.py` / `python -m pkg.mod` a document tells its
    reader to run, and every `dir/file.py` it names, is in the tree."""
    text = (REPO / doc).read_text()
    tree = _tree_py()

    def in_tree(path):
        return any(f == path or f.endswith("/" + path) for f in tree)

    gone = []
    for mod, path in re.findall(
            r"python3?\s+(?:-m\s+([\w.]+)|([\w./-]+\.py))", text):
        if path and not (REPO / path).is_file():
            gone.append(path)
        if mod.startswith("jepsen_jgroups_raft_tpu"):
            base = mod.replace(".", "/")
            if not in_tree(base + ".py") and \
                    not in_tree(base + "/__main__.py"):
                gone.append(mod)
    gone += [t for t in re.findall(r"(?<![\w/.-])([\w.-]+/[\w./-]*\w\.py)\b",
                                   text) if not in_tree(t)]
    assert not gone, (doc, sorted(set(gone)))


# ------------------------------------------- knob-parse regressions


class TestKnobParsing:
    def test_env_str_blank_means_unset(self, monkeypatch):
        monkeypatch.setenv("JGRAFT_SERVICE_CLUSTER_DIR", "   ")
        assert env_str("JGRAFT_SERVICE_CLUSTER_DIR") == ""
        monkeypatch.setenv("JGRAFT_SERVICE_CLUSTER_DIR", " /shared ")
        assert env_str("JGRAFT_SERVICE_CLUSTER_DIR") == "/shared"
        monkeypatch.delenv("JGRAFT_SERVICE_CLUSTER_DIR")
        assert env_str("JGRAFT_SERVICE_CLUSTER_DIR", "dflt") == "dflt"

    def test_cluster_dir_blank_is_inert(self, monkeypatch):
        from jepsen_jgroups_raft_tpu.service import store
        monkeypatch.setenv("JGRAFT_SERVICE_CLUSTER_DIR", "  ")
        assert store.cluster_dir() is None

    def test_watchdog_margin_keeps_fractional_seconds(self, monkeypatch):
        # regression: float(env_int(...)) silently discarded "0.5"
        from jepsen_jgroups_raft_tpu.service import daemon
        monkeypatch.setenv("JGRAFT_SERVICE_WATCHDOG_S", "0.5")
        assert daemon.default_watchdog_margin() == 0.5
        monkeypatch.setenv("JGRAFT_SERVICE_WATCHDOG_S", "banana")
        assert daemon.default_watchdog_margin() == 30.0

    def test_importers_survive_garbage_knobs(self):
        # the PR 7 rule: a blank or garbage knob must never crash an
        # importer (module-level raw float()/int() parses used to)
        for raw in ("garbage", "", " "):
            env = dict(os.environ, JGRAFT_SERVICE_WATCHDOG_S=raw,
                       JGRAFT_SCAN_CHUNK=raw, JGRAFT_SERVICE_WORKERS=raw,
                       JAX_PLATFORMS="cpu")
            out = subprocess.run(
                [sys.executable, "-c",
                 "import jepsen_jgroups_raft_tpu.checker.linearizable\n"
                 "import chip_smoke\n"
                 "from jepsen_jgroups_raft_tpu.service import daemon\n"
                 "print(daemon.default_watchdog_margin())"],
                cwd=REPO, env=env, capture_output=True, text=True,
                timeout=120)
            assert out.returncode == 0, out.stderr
            assert out.stdout.split() == ["30.0"], out.stdout


# ------------------------------------------------------ CLI workflow


class TestCliWorkflow:
    def test_knob_registry_artifact(self, tmp_path, capsys):
        reg_file = tmp_path / "knob_registry.json"
        rc = cli.main(["--rules", "envknobs",
                       "--knob-registry", str(reg_file)])
        capsys.readouterr()
        assert rc == 0
        reg = json.loads(reg_file.read_text())
        assert reg["version"] == 2 and reg["knobs"]
        site = reg["knobs"]["JGRAFT_SERVICE_WATCHDOG_S"]["sites"][0]
        assert site["via"] == "env_float"
        assert site["path"].endswith("service/daemon.py")

    def test_sarif_help_uris_point_at_section_18(self):
        sarif = report.to_sarif([], [], list(cli.RULES["guarded"]) +
                                list(cli.RULES["crashproto"]),
                                rule_help=cli.RULE_HELP)
        rules = sarif["runs"][0]["tool"]["driver"]["rules"]
        assert rules
        for r in rules:
            assert "#18-concurrency" in r["helpUri"], r

    def test_repo_clean_under_all_ten_analyzers(self):
        findings = cli.run([str(PKG), str(REPO / "native" / "src")],
                           list(cli.ANALYZERS))
        assert not findings, findings

    def test_shipped_baseline_is_empty(self):
        base = json.loads(
            (PKG / "lint" / "baseline.json").read_text())
        assert base["findings"] == []

    def test_graftsync_rules_are_registered(self):
        listed = {r for rules in cli.RULES.values() for r in rules}
        for rule in (guarded.RULE, lockorder.RULE_CYCLE,
                     lockorder.RULE_ORDER, lockorder.RULE_RANK,
                     crashproto.RULE_FSYNC, crashproto.RULE_INPLACE,
                     crashproto.RULE_SHUTIL, envknobs.RULE_RAW,
                     envknobs.RULE_DOC, envknobs.RULE_DUP):
            assert rule in listed, rule
            assert rule in cli.RULE_HELP, rule
