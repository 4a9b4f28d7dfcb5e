"""graftcheck (lint/flow) tests — ISSUE 2 tentpole.

Same stance as test_lint.py: every rule is proven to FIRE on a seeded
violation (a checker that cannot fire is indistinguishable from one that
does not run) and to stay QUIET on the fixed repo; plus CFG-construction
fixtures for the control shapes the analyzers lean on
(try/finally/with/early-return, exception edges), the acceptance-named
mis-sized-BlockSpec rejection, the in-memory mutation test against the
real nemesis sources, and the baseline/SARIF CLI workflow. Tier-1,
CPU-only, no jax import anywhere in the analyzers.
"""

import json
from pathlib import Path

from jepsen_jgroups_raft_tpu.lint import cli, report
from jepsen_jgroups_raft_tpu.lint.base import SourceFile
from jepsen_jgroups_raft_tpu.lint.flow import heal, kernel_contract, resource
from jepsen_jgroups_raft_tpu.lint.flow.cfg import EXC, FALSE, TRUE, cfg_for

REPO = Path(__file__).resolve().parents[1]
PKG = REPO / "jepsen_jgroups_raft_tpu"


def rules_of(findings):
    return {f.rule for f in findings}


# ------------------------------------------------------------------- CFG


def succ_kinds(node):
    return {k for _, k in node.succs}


def reaches(cfg, start, target, kinds=None):
    seen, stack = set(), [start]
    while stack:
        n = stack.pop()
        if n is target:
            return True
        if n.idx in seen:
            continue
        seen.add(n.idx)
        stack.extend(s for s, k in n.succs if kinds is None or k in kinds)
    return False


class TestCfgConstruction:
    def test_if_has_branch_arms_and_exception_edge(self):
        g = cfg_for("def f(x):\n"
                    "    if check(x):\n"
                    "        return 1\n"
                    "    return 2\n", "f")
        [cond] = g.find("if")
        assert {TRUE, FALSE, EXC} <= succ_kinds(cond)

    def test_try_finally_duplicates_finally_per_continuation(self):
        g = cfg_for("def f(x):\n"
                    "    try:\n"
                    "        risky(x)\n"
                    "        return 1\n"
                    "    finally:\n"
                    "        cleanup(x)\n", "f")
        # separate instances: exception path, return path, normal path
        assert len(g.find("finally")) == 3
        # the exception edge of risky() reaches raise_exit THROUGH a
        # cleanup node, never directly
        risky = next(n for n in g.stmt_nodes() if n.line == 3)
        direct = [d for d, k in risky.succs if d is g.raise_exit]
        assert not direct
        assert reaches(g, risky, g.raise_exit)

    def test_early_return_routes_through_finally(self):
        g = cfg_for("def f(x):\n"
                    "    try:\n"
                    "        if x:\n"
                    "            return early()\n"
                    "    finally:\n"
                    "        cleanup(x)\n"
                    "    return late()\n", "f")
        [ret] = [n for n in g.find("return") if n.line == 4]
        # the return's continuation is a finally instance, not exit
        succs = [d for d, k in ret.succs if k != EXC]
        assert all(d.label == "finally" for d in succs)
        assert reaches(g, ret, g.exit)

    def test_with_exception_routes_through_exit_marker(self):
        g = cfg_for("def f():\n"
                    "    with open('x') as fh:\n"
                    "        risky(fh)\n"
                    "    return 1\n", "f")
        risky = next(n for n in g.stmt_nodes() if n.line == 3)
        exc_succ = [d for d, k in risky.succs if k == EXC]
        assert exc_succ and all(d.label == "with-exit" for d in exc_succ)
        assert reaches(g, risky, g.raise_exit)

    def test_while_true_only_leaves_via_break(self):
        g = cfg_for("def f(q):\n"
                    "    while True:\n"
                    "        v = q.get()\n"
                    "        if v is None:\n"
                    "            break\n", "f")
        [loop] = g.find("while")
        assert FALSE not in succ_kinds(loop)
        [brk] = g.find("break")
        assert reaches(g, brk, g.exit)

    def test_non_catchall_handler_keeps_propagate_edge(self):
        g = cfg_for("def f(x):\n"
                    "    try:\n"
                    "        risky(x)\n"
                    "    except ValueError:\n"
                    "        handle(x)\n"
                    "    return 1\n", "f")
        [dispatch] = g.find("except-dispatch")
        assert any(d is g.raise_exit for d, _ in dispatch.succs)
        # with a catch-all instead, the propagate edge disappears
        g2 = cfg_for("def f(x):\n"
                     "    try:\n"
                     "        risky(x)\n"
                     "    except Exception:\n"
                     "        handle(x)\n"
                     "    return 1\n", "f")
        [dispatch2] = g2.find("except-dispatch")
        assert not any(d is g2.raise_exit for d, _ in dispatch2.succs)


# -------------------------------------------------------- kernel contract


def kc(snippet, path="fixture.py"):
    return kernel_contract.analyze_source(SourceFile.from_text(path, snippet))


FIXTURE_KERNEL = """
import jax
from jax.experimental import pallas as pl

def build():
    C = 128
    def call(x):
        return pl.pallas_call(
            kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((40, C), lambda g: (g, 0))],
            out_specs=pl.BlockSpec((8, C), lambda g: (g, 0)),
            out_shape=jax.ShapeDtypeStruct((32, C), jnp.int32),
        )(x)
    return call
"""


class TestKernelContract:
    def test_every_contract_names_a_file_of_the_tree(self):
        # a binding for a deleted module fails here, not at the next
        # scripts/lint.sh: the CLI only scans the files it finds
        gone = [f for f in kernel_contract.CONTRACTS
                if not (PKG / f).is_file()]
        assert not gone, gone

    def test_production_shape_files_clean(self):
        for f in ("ops/kernel_ir.py", "ops/dense_scan.py",
                  "ops/linear_scan.py", "parallel/mesh.py"):
            src = SourceFile.load(PKG / Path(f))
            assert kernel_contract.analyze_source(src) == [], f

    def test_chunked_dense_carry_contract_fires_on_inflated_carry(self):
        # ISSUE-3 binding, now proven ONCE against the kernel IR (PR 6):
        # the chunked kernels keep per-row scan state resident BETWEEN
        # launches; inflating the carry accounting past VMEM at the
        # eligibility caps must fail the gate.
        text = (PKG / "ops" / "kernel_ir.py").read_text()
        # (since ISSUE 41 the frontier is a uint32 word a configuration)
        assert "(1 << n_slots) * 4                 # F words" in text
        mutated = text.replace("(1 << n_slots) * 4                 # F",
                               "(1 << n_slots) * 4 * 4096          # F")
        found = kc(mutated, path="ops/kernel_ir.py")
        assert "kernel-vmem-budget" in rules_of(found)

    def test_chunked_sort_carry_contract_fires_on_inflated_carry(self):
        text = (PKG / "ops" / "kernel_ir.py").read_text()
        assert "n_configs * k * 4 + n_configs * 4" in text
        mutated = text.replace("n_configs * k * 4 + n_configs * 4",
                               "n_configs * k * 4096 + n_configs * 4")
        found = kc(mutated, path="ops/kernel_ir.py")
        assert "kernel-vmem-budget" in rules_of(found)

    def test_cycle_adjacency_contract_fires_on_inflated_slab(self):
        # ISSUE-13 binding: the cycle-closure kernel keeps the int32
        # adjacency + product slab resident per row; inflating the
        # accounting past VMEM at CYCLE_MAX_NODES must fail the gate.
        text = (PKG / "ops" / "kernel_ir.py").read_text()
        assert "2 * n_nodes * n_nodes * 4" in text
        mutated = text.replace("2 * n_nodes * n_nodes * 4",
                               "2 * n_nodes * n_nodes * 4096")
        found = kc(mutated, path="ops/kernel_ir.py")
        assert "kernel-vmem-budget" in rules_of(found)

    def test_chunk_carry_binding_is_loud_when_fn_vanishes(self):
        # Renaming the accounting fn must FAIL the gate (loud), not
        # silently drop the chunked-carry invariant — for BOTH families'
        # accounting in the IR (and the ISSUE-13 cycle slab's).
        text = (PKG / "ops" / "kernel_ir.py").read_text()
        for fn in ("dense_chunk_carry_bytes", "sort_chunk_carry_bytes",
                   "cycle_adjacency_bytes"):
            mutated = text.replace(f"def {fn}", "def renamed_carry_bytes")
            found = kc(mutated, path="ops/kernel_ir.py")
            # The loud path must surface under kernel-unresolved (NOT
            # kernel-vmem-budget): a baselined budget rule must never
            # swallow a vanished accounting fn.
            assert any(f.rule == "kernel-unresolved"
                       and "not resolvable" in f.message
                       for f in found), fn

    def test_well_formed_fixture_is_clean(self):
        assert kc(FIXTURE_KERNEL) == []

    def test_missized_blockspec_rejected(self):
        # the acceptance-named case: block dim 7 does not divide the
        # declared out dim 32
        bad = FIXTURE_KERNEL.replace("pl.BlockSpec((8, C), lambda g: (g, 0))",
                                     "pl.BlockSpec((7, C), lambda g: (g, 0))")
        assert "kernel-block-divide" in rules_of(kc(bad))

    def test_grid_cover_mismatch_rejected(self):
        # 4 programs × 8 rows = 32 ✓ but out_shape says 64: half the
        # output is never written
        bad = FIXTURE_KERNEL.replace("(32, C)", "(64, C)")
        assert "kernel-grid-cover" in rules_of(kc(bad))

    def test_mosaic_tile_rule(self):
        # lane dim 100: neither a multiple of 128 nor the full dim
        bad = FIXTURE_KERNEL.replace("C = 128", "C = 100").replace(
            "jax.ShapeDtypeStruct((32, C)",
            "jax.ShapeDtypeStruct((32, 200)")
        assert "kernel-block-tile" in rules_of(kc(bad))

    def test_x64_dtype_rejected(self):
        bad = FIXTURE_KERNEL.replace("jnp.int32", "jnp.float64")
        assert "kernel-dtype" in rules_of(kc(bad))

    def test_vmem_budget_enforced(self):
        bad = FIXTURE_KERNEL.replace("(40, C)", "(40960, 1024)")
        assert "kernel-vmem-budget" in rules_of(kc(bad))
        # and the budget is configurable
        src = SourceFile.from_text("fixture.py", bad)
        big = kernel_contract.analyze_source(src, vmem_budget=1 << 30)
        assert "kernel-vmem-budget" not in rules_of(big)

    def test_unresolved_is_loud_not_silent(self):
        # a symbolic shape with no contract must FAIL, not pass
        bad = FIXTURE_KERNEL.replace("def build():", "def build(E):") \
                            .replace("(40, C)", "(E * 5, C)")
        assert "kernel-unresolved" in rules_of(kc(bad))

    def test_budget_const_contract_fires_on_mutated_budget(self):
        # kernel_ir's contract pins the dense cell cap under usable
        # VMEM; inflating it must fail the gate
        text = (PKG / "ops" / "kernel_ir.py").read_text()
        assert "DENSE_MAX_CELLS = 65536" in text
        mutated = text.replace("DENSE_MAX_CELLS = 65536",
                               "DENSE_MAX_CELLS = 65536 << 7")
        found = kc(mutated, path="ops/kernel_ir.py")
        assert any(f.rule == "kernel-vmem-budget"
                   and "DENSE_MAX_CELLS * 4" in f.message for f in found)


# ------------------------------------------------------------------ heal


def hl(snippet):
    return heal.analyze_source(SourceFile.from_text("seed.py", snippet))


class TestHealPairing:
    def test_nemesis_tier_clean(self):
        for f in ("faults.py", "membership.py", "package.py", "base.py"):
            src = SourceFile.load(PKG / "nemesis" / f)
            assert heal.analyze_source(src) == [], f

    def test_seeded_unhealed_fires(self):
        snippet = ("class Nem:\n"
                   "    def invoke(self, test, node):\n"
                   "        self.db.kill(test, node)\n"
                   "        return 'done'\n")
        [f] = hl(snippet)
        assert f.rule == "flow-unhealed-fault" and f.line == 3

    def test_finally_heal_alone_is_not_enough(self):
        # the heal lives in a finally — but the heal call itself can
        # raise, and then the affliction is live with nothing tracking
        # it (exactly the membership rollback bug). Strict by design.
        snippet = ("class Nem:\n"
                   "    def invoke(self, test, node):\n"
                   "        self.db.kill(test, node)\n"
                   "        try:\n"
                   "            probe(test)\n"
                   "        finally:\n"
                   "            self.db.start(test, node)\n"
                   "        return 'done'\n")
        [f] = hl(snippet)
        assert f.rule == "flow-unhealed-fault"
        # registration right after the fault makes the same shape sound:
        # teardown owns whatever the heal failed to undo
        fixed = snippet.replace(
            "        try:\n",
            "        self.afflicted.add(node)\n        try:\n")
        assert hl(fixed) == []

    def test_exception_path_skipping_heal_fires(self):
        # heal only on the normal path: the exception edge of probe()
        # escapes un-healed
        snippet = ("class Nem:\n"
                   "    def invoke(self, test, node):\n"
                   "        self.db.kill(test, node)\n"
                   "        probe(test)\n"
                   "        self.db.start(test, node)\n"
                   "        return 'done'\n")
        [f] = hl(snippet)
        assert "exception path" in f.message

    def test_raising_heal_does_not_discharge(self):
        # the membership bug shape: the rollback heal itself raises and
        # is swallowed — the fault is still live
        snippet = ("class Nem:\n"
                   "    def invoke(self, test, node):\n"
                   "        self.db.kill(test, node)\n"
                   "        try:\n"
                   "            self.db.start(test, node)\n"
                   "        except Exception:\n"
                   "            pass\n")
        [f] = hl(snippet)
        assert f.rule == "flow-unhealed-fault"

    def test_registration_discharges(self):
        snippet = ("class Nem:\n"
                   "    def invoke(self, test, node):\n"
                   "        self.db.kill(test, node)\n"
                   "        self.afflicted.add(node)\n"
                   "        return 'done'\n")
        assert hl(snippet) == []

    def test_blanket_teardown_discharges_but_registry_loop_does_not(self):
        blanket = ("class Nem:\n"
                   "    def invoke(self, test, g):\n"
                   "        self.net.partition(test, g)\n"
                   "        return 'cut'\n"
                   "    def teardown(self, test):\n"
                   "        self.net.heal(test)\n")
        assert hl(blanket) == []
        registry = ("class Nem:\n"
                    "    def invoke(self, test, node):\n"
                    "        self.db.kill(test, node)\n"
                    "        return 'done'\n"
                    "    def teardown(self, test):\n"
                    "        for n in sorted(self.afflicted):\n"
                    "            self.db.start(test, n)\n")
        # a registry-driven teardown only covers REGISTERED afflictions
        assert rules_of(hl(registry)) == {"flow-unhealed-fault"}

    def test_inherited_teardown_counts(self):
        snippet = ("class Base:\n"
                   "    def teardown(self, test):\n"
                   "        self.net.heal(test)\n"
                   "class Nem(Base):\n"
                   "    def invoke(self, test, g):\n"
                   "        self.net.partition(test, g)\n"
                   "        return 'cut'\n")
        assert hl(snippet) == []
        # and without the inherited teardown it fires
        alone = snippet.replace("class Base:\n"
                                "    def teardown(self, test):\n"
                                "        self.net.heal(test)\n", "")
        assert rules_of(hl(alone)) == {"flow-unhealed-fault"}

    def test_pragma_suppresses(self):
        snippet = ("class Nem:\n"
                   "    def invoke(self, test, node):\n"
                   "        self.db.kill(test, node)  # lint: "
                   "allow(unhealed)\n"
                   "        return 'killed'\n")
        assert hl(snippet) == []
        # pragma removed -> fires (it is load-bearing, not decoration)
        assert rules_of(hl(snippet.replace(
            "  # lint: allow(unhealed)", ""))) == {"flow-unhealed-fault"}

    def test_delegating_wrapper_is_the_primitive(self):
        snippet = ("class Nem:\n"
                   "    def _do(self, test, node):\n"
                   "        self.db.kill(test, node)\n")
        assert hl(snippet) == []

    # --- mutation tests against the REAL nemesis sources -------------

    def test_mutation_teardown_heal_deleted_from_faults(self):
        text = (PKG / "nemesis" / "faults.py").read_text()
        marker = ("    def teardown(self, test):\n"
                  "        # Never leave the network cut after a run.\n"
                  "        try:\n"
                  "            self.net.heal(test)\n"
                  "        except Exception:\n"
                  "            pass")
        assert marker in text
        mutated = text.replace(marker,
                               "    def teardown(self, test):\n"
                               "        pass")
        found = heal.analyze_source(
            SourceFile.from_text("faults.py", mutated))
        assert any(f.rule == "flow-unhealed-fault" and
                   "`partition`" in f.message for f in found)

    def test_mutation_registration_deleted_from_faults(self):
        text = (PKG / "nemesis" / "faults.py").read_text()
        assert "self.afflicted.add(n)" in text
        mutated = text.replace("self.afflicted.add(n)", "pass")
        found = heal.analyze_source(
            SourceFile.from_text("faults.py", mutated))
        assert any(f.rule == "flow-unhealed-fault" and "`_do`" in f.message
                   for f in found)

    def test_membership_pragmas_are_load_bearing(self):
        # the allow(unhealed) inventory: exactly the two deliberate
        # sites, and removing one re-arms the analyzer
        text = (PKG / "nemesis" / "membership.py").read_text()
        assert text.count("lint: allow(unhealed)") == 2
        mutated = text.replace(
            "self.db.kill(test, node)  # lint: allow(unhealed)",
            "self.db.kill(test, node)")
        found = heal.analyze_source(
            SourceFile.from_text("membership.py", mutated))
        assert any(f.rule == "flow-unhealed-fault" and "`kill`" in f.message
                   for f in found)


# --------------------------------------------------------------- resource


def rl(snippet):
    return resource.analyze_source(SourceFile.from_text("seed.py", snippet))


class TestResourceLeak:
    def test_deploy_runner_tier_clean(self):
        for f in ("deploy/ssh.py", "deploy/local.py", "core/runner.py",
                  "core/db.py"):
            src = SourceFile.load(PKG / Path(f))
            assert resource.analyze_source(src) == [], f

    # regression fixtures: each FIXED bug's pre-fix shape must fire and
    # its fixed shape must stay quiet.

    def test_log_handle_leak_shape(self):
        # deploy/local.py start_node pre-fix: Popen raises -> open log
        # handle leaks (Popen is not an adopting callee)
        bad = ("def start_node(self, name):\n"
               "    log = open(self.log_path(name), 'ab')\n"
               "    self.procs[name] = Popen(['bin'], stdout=log)\n"
               "    log.close()\n")
        [f] = rl(bad)
        assert f.rule == "flow-resource-leak" and f.line == 2
        good = ("def start_node(self, name):\n"
                "    with open(self.log_path(name), 'ab') as log:\n"
                "        self.procs[name] = Popen(['bin'], stdout=log)\n")
        assert rl(good) == []

    def test_half_open_client_shape(self):
        # core/runner.py pre-fix: setup raises -> handler drops the open
        # connection by reassigning None
        bad = ("def worker(proto, test, node):\n"
               "    try:\n"
               "        client = proto.open(test, node)\n"
               "        client.setup(test)\n"
               "    except Exception:\n"
               "        client = None\n"
               "    return client\n")
        [f] = rl(bad)
        assert "reassigns" in f.message
        good = ("def worker(proto, test, node):\n"
                "    client = proto.open(test, node)\n"
                "    try:\n"
                "        client.setup(test)\n"
                "    except BaseException:\n"
                "        try:\n"
                "            client.close(test)\n"
                "        except Exception:\n"
                "            LOG.debug('half-open close failed')\n"
                "        raise\n"
                "    return client\n")
        assert rl(good) == []

    def test_teardown_then_close_shape(self):
        # core/runner.py pre-fix finally: a raising teardown skips close
        bad = ("def worker(proto, test, node):\n"
               "    client = proto.open(test, node)\n"
               "    try:\n"
               "        use(client)\n"
               "    finally:\n"
               "        try:\n"
               "            client.teardown(test)\n"
               "            client.close(test)\n"
               "        except Exception:\n"
               "            LOG.exception('teardown failed')\n")
        [f] = rl(bad)
        assert f.rule == "flow-resource-leak"
        good = bad.replace(
            "            client.teardown(test)\n"
            "            client.close(test)\n"
            "        except Exception:\n"
            "            LOG.exception('teardown failed')\n",
            "            client.teardown(test)\n"
            "        finally:\n"
            "            client.close(test)\n")
        assert rl(good) == []

    def test_bind_before_adoption_shape(self):
        # deploy/local.py _free_ports pre-fix: bind raises before append
        bad = ("def free_ports(n):\n"
               "    socks = []\n"
               "    try:\n"
               "        for _ in range(n):\n"
               "            s = socket.socket()\n"
               "            s.bind(('127.0.0.1', 0))\n"
               "            socks.append(s)\n"
               "        return [s.getsockname()[1] for s in socks]\n"
               "    finally:\n"
               "        for s in socks:\n"
               "            s.close()\n")
        [f] = rl(bad)
        assert f.line == 5
        good = bad.replace("            s.bind(('127.0.0.1', 0))\n"
                           "            socks.append(s)\n",
                           "            socks.append(s)\n"
                           "            s.bind(('127.0.0.1', 0))\n")
        assert rl(good) == []

    def test_close_in_finally_with_none_guard_is_quiet(self):
        snippet = ("def probe(name):\n"
                   "    conn = None\n"
                   "    try:\n"
                   "        conn = NativeConn(name, 9000)\n"
                   "        return conn.probe()\n"
                   "    except CONN_ERRORS:\n"
                   "        return None\n"
                   "    finally:\n"
                   "        if conn is not None:\n"
                   "            conn.close()\n")
        assert rl(snippet) == []

    def test_return_transfers_ownership(self):
        snippet = ("def admin(name):\n"
                   "    conn = NativeConn(name, 9000)\n"
                   "    return conn\n")
        assert rl(snippet) == []

    def test_attempted_release_discharges(self):
        # a close that raises still counts as released (attempted)
        snippet = ("def shut(name):\n"
                   "    conn = NativeConn(name, 9000)\n"
                   "    try:\n"
                   "        conn.close()\n"
                   "    except Exception:\n"
                   "        LOG.debug('close failed')\n")
        assert rl(snippet) == []

    def test_pragma_suppresses(self):
        snippet = ("def leak(name):\n"
                   "    conn = NativeConn(name, 9000)  # lint: "
                   "allow(resource-leak)\n"
                   "    ping(conn)\n")
        assert rl(snippet) == []


class TestServiceResourceScope:
    """ISSUE-5 satellite: the analyzer's scan set covers the service
    tier (graftd holds queue entries, per-call client sockets, trace
    file handles, and worker threads across exception paths — and it is
    long-lived, so a per-request leak exhausts the daemon's fds where a
    one-shot run never notices). Scope + shipped-clean + the mutation
    proving the analyzer FIRES on the real service source."""

    SERVICE_FILES = ("service/request.py", "service/admission.py",
                     "service/scheduler.py", "service/daemon.py",
                     "service/http.py", "service/client.py")

    def test_scope_covers_service_package(self):
        for f in self.SERVICE_FILES:
            assert resource.applies_to(f"jepsen_jgroups_raft_tpu/{f}"), f
        assert not resource.applies_to(
            "jepsen_jgroups_raft_tpu/checker/linearizable.py")

    def test_service_tier_clean(self):
        for f in self.SERVICE_FILES:
            src = SourceFile.load(PKG / Path(f))
            assert resource.analyze_source(src) == [], f

    def test_trace_handle_mutation_fires(self):
        # daemon._write_trace holds the results.json temp-file handle
        # in a `with` (the publish is temp-write + os.replace since the
        # crash-consistency pass); demoting it to a bare open() must
        # re-arm the analyzer on the REAL source (the exception edge
        # out of json.dump then escapes without a close).
        text = (PKG / "service" / "daemon.py").read_text()
        managed = ('tmp = d / "results.json.tmp"\n'
                   '            with open(tmp, "w") as f:\n'
                   '                json.dump(payload, f, indent=2)')
        assert managed in text  # the mutation target must exist
        mutated = text.replace(
            managed,
            'tmp = d / "results.json.tmp"\n'
            '            f = open(tmp, "w")\n'
            '            json.dump(payload, f, indent=2)')
        assert mutated != text
        found = resource.analyze_source(
            SourceFile.from_text("daemon.py", mutated))
        assert any(f.rule == "flow-resource-leak" and "`f`" in f.message
                   for f in found)

    def test_submit_socket_leak_shape(self):
        # the client-socket-per-submission shape: a raising request()
        # path escapes with the socket open
        bad = ("def push(netloc, payload):\n"
               "    sock = create_connection(netloc)\n"
               "    sock.sendall(payload)\n"
               "    sock.close()\n")
        [f] = rl(bad)
        assert f.rule == "flow-resource-leak" and f.line == 2
        good = ("def push(netloc, payload):\n"
                "    sock = create_connection(netloc)\n"
                "    try:\n"
                "        sock.sendall(payload)\n"
                "    finally:\n"
                "        sock.close()\n")
        assert rl(good) == []

    def test_queue_entry_trace_handle_shape(self):
        # queue-entry bookkeeping that opens a per-request trace file
        # and loses it when the write raises mid-loop
        bad = ("def drain(entries, root):\n"
               "    for e in entries:\n"
               "        trace = open(root / e.id, 'w')\n"
               "        trace.write(e.payload)\n"
               "        trace.close()\n")
        [f] = rl(bad)
        assert f.rule == "flow-resource-leak"
        good = bad.replace(
            "        trace = open(root / e.id, 'w')\n"
            "        trace.write(e.payload)\n"
            "        trace.close()\n",
            "        with open(root / e.id, 'w') as trace:\n"
            "            trace.write(e.payload)\n")
        assert rl(good) == []


# ------------------------------------------------------- CLI + baseline


BAD_NEMESIS = ("class Nem:\n"
               "    def invoke(self, test, node):\n"
               "        self.db.kill(test, node)\n"
               "        return 'done'\n")


class TestDistributedTierResourceScope:
    """ISSUE-7 satellite: the multi-process launcher holds Popen
    handles and the coordinator-port socket across exception paths —
    a leaked child is a whole wedged interpreter, not just an fd."""

    FILES = ("parallel/distributed.py", "parallel/launch.py")

    def test_scope_covers_distributed_tier(self):
        for f in self.FILES:
            assert resource.applies_to(f"jepsen_jgroups_raft_tpu/{f}"), f

    def test_distributed_tier_clean(self):
        for f in self.FILES:
            src = SourceFile.load(PKG / Path(f))
            assert resource.analyze_source(src) == [], f

    def test_scope_covers_durability_tier(self):
        # ISSUE-8 satellite: the journal rides the service/ prefix;
        # the chaos harness (daemon subprocesses + sockets across
        # kill/restart cycles) is scanned by explicit path — and both
        # must be CLEAN (shipped baseline stays empty).
        assert resource.applies_to(
            "jepsen_jgroups_raft_tpu/service/journal.py")
        assert resource.applies_to("scripts/chaos_graftd.py")
        for path in (PKG / "service" / "journal.py",
                     PKG.parent / "scripts" / "chaos_graftd.py"):
            src = SourceFile.load(path)
            assert resource.analyze_source(src) == [], str(path)

    def test_scope_covers_cluster_tier(self):
        # ISSUE-11 satellite: the result store publishes via temp
        # files + os.replace and the cluster manager holds lease and
        # claimed-journal handles — a leaked temp or handle on an
        # exception path would accrete forever in a shared dir every
        # replica scans. Both ride the service/ prefix and must be
        # CLEAN (shipped baseline stays empty).
        for f in ("service/store.py", "service/cluster.py"):
            assert resource.applies_to(f"jepsen_jgroups_raft_tpu/{f}"), f
            src = SourceFile.load(PKG / Path(f))
            assert resource.analyze_source(src) == [], f

    def test_launcher_unkilled_popen_shape_fires(self):
        # launch_local_cluster adopts every child into `procs` inside
        # a try whose finally kills survivors; a bare spawn whose
        # readiness check can raise is exactly the leak shape the
        # widened scope exists to catch — proves it is not vacuous.
        bad = ("import subprocess\n"
               "def spawn(cmd, env, check):\n"
               "    p = subprocess.Popen(cmd, env=env)\n"
               "    check(p)\n"
               "    return p.pid\n")
        src = SourceFile.from_text(
            "jepsen_jgroups_raft_tpu/parallel/launch.py", bad)
        assert any(f.rule == "flow-resource-leak"
                   for f in resource.analyze_source(src))


class TestCliFlow:
    def test_repo_is_clean_under_all_six(self):
        findings = cli.run(
            [str(PKG), str(REPO / "native" / "src")],
            ["taxonomy", "jit", "lock", "kernel", "heal", "resource"])
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_list_rules_includes_flow_tier(self, capsys):
        assert cli.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in ("kernel-block-divide", "flow-unhealed-fault",
                     "flow-resource-leak"):
            assert rule in out

    def test_sarif_output_shape(self, tmp_path, capsys):
        bad = tmp_path / "seed.py"
        bad.write_text(BAD_NEMESIS)
        rc = cli.main([str(bad), "--format", "json",
                       "--baseline", str(tmp_path / "none.json")])
        out = capsys.readouterr().out
        sarif = json.loads(out)
        assert rc == 1
        assert sarif["version"] == "2.1.0"
        [run] = sarif["runs"]
        assert run["tool"]["driver"]["name"] == "graftlint"
        assert any(r["ruleId"] == "flow-unhealed-fault"
                   for r in run["results"])
        loc = run["results"][0]["locations"][0]["physicalLocation"]
        assert loc["region"]["startLine"] >= 1

    def test_baseline_gates_only_regressions(self, tmp_path, capsys):
        bad = tmp_path / "seed.py"
        bad.write_text(BAD_NEMESIS)
        bp = tmp_path / "baseline.json"
        # 1. accept the pre-existing finding
        assert cli.main([str(bad), "--baseline", str(bp),
                         "--update-baseline"]) == 0
        assert bp.exists()
        # 2. baselined -> clean exit, finding suppressed
        assert cli.main([str(bad), "--baseline", str(bp)]) == 0
        assert "baselined" in capsys.readouterr().out
        # 3. a NEW violation still gates
        bad.write_text(BAD_NEMESIS +
                       "    def stop(self, test, node):\n"
                       "        self.db.pause(test, node)\n"
                       "        return 'paused'\n")
        assert cli.main([str(bad), "--baseline", str(bp)]) == 1
        out = capsys.readouterr().out
        assert "`pause`" in out and "`kill`" not in out
        # 4. SARIF marks the baselined result suppressed
        rc = cli.main([str(bad), "--format", "json",
                       "--baseline", str(bp)])
        assert rc == 1
        sarif = json.loads(capsys.readouterr().out)
        sup = [bool(r["suppressions"])
               for r in sarif["runs"][0]["results"]]
        assert sorted(sup) == [False, True]

    def test_shipped_baseline_is_empty(self):
        # acceptance: the repo lints clean with an EMPTY baseline — the
        # real findings were fixed, not baselined
        data = json.loads((PKG / "lint" / "baseline.json").read_text())
        assert data["findings"] == []

    def test_fingerprints_survive_line_drift(self, tmp_path):
        f1 = tmp_path / "a.py"
        f1.write_text(BAD_NEMESIS)
        from jepsen_jgroups_raft_tpu.lint.flow import heal as h
        [finding] = h.analyze_file(f1)
        finding = finding.__class__("a.py", finding.line, finding.rule,
                                    finding.message)
        [(_, fp1)] = report.fingerprints([finding], tmp_path)
        # shift the finding two lines down: same content -> same print
        f1.write_text("# header\n# header\n" + BAD_NEMESIS)
        [finding2] = h.analyze_file(f1)
        finding2 = finding2.__class__("a.py", finding2.line, finding2.rule,
                                      finding2.message)
        [(_, fp2)] = report.fingerprints([finding2], tmp_path)
        assert fp1 == fp2


class TestReviewFixes:
    """Regressions for the findings of this PR's code review."""

    def test_interpreter_abort_degrades_to_unresolved_not_crash(self):
        # a loop past the interpreter's iteration ceiling in the
        # enclosing scope must not crash the lint run
        hot = FIXTURE_KERNEL.replace(
            "    C = 128\n",
            "    C = 0\n    for i in range(200001):\n        C = C + 1\n")
        found = kc(hot)  # must not raise
        assert rules_of(found) == {"kernel-unresolved"}

    def test_default_blockspec_without_index_map_is_not_a_tile_violation(
            self):
        # no index_map = whole-array block: spans the full dims by
        # definition, so the Mosaic tile rule cannot fire on it
        snippet = FIXTURE_KERNEL.replace(
            "pl.BlockSpec((40, C), lambda g: (g, 0))",
            "pl.BlockSpec((3, 64))")
        assert "kernel-block-tile" not in rules_of(kc(snippet))

    def test_partial_update_baseline_merges_not_clobbers(self, tmp_path):
        bad = tmp_path / "seed.py"
        bad.write_text(BAD_NEMESIS)
        leak = tmp_path / "leak.py"
        leak.write_text("def f(name):\n"
                        "    conn = NativeConn(name, 9000)\n"
                        "    ping(conn)\n")
        bp = tmp_path / "bl.json"
        assert cli.main([str(bad), "--rules", "heal",
                         "--baseline", str(bp), "--update-baseline"]) == 0
        n1 = len(report.load_baseline(bp))
        assert n1 == 1
        # a second partial update for a DIFFERENT analyzer/path must
        # keep the first fingerprint
        assert cli.main([str(leak), "--rules", "resource",
                         "--baseline", str(bp), "--update-baseline"]) == 0
        assert len(report.load_baseline(bp)) == n1 + 1
        # both gates now pass against the merged baseline
        assert cli.main([str(bad), "--rules", "heal",
                         "--baseline", str(bp)]) == 0
        assert cli.main([str(leak), "--rules", "resource",
                         "--baseline", str(bp)]) == 0
