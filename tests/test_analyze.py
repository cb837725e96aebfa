"""dmlc-analyze fixtures: each interprocedural rule fires on its seeded
multi-module defect package, stays silent on the fixed variant, prints a
full call-chain witness, and respects the shared suppression escape hatch.
The final tests run the real CLI over the real tree (the repo itself must
analyze clean — the acceptance bar tools/ci_check.sh enforces) and pin the
JSON schema shared between ``tools.lint --json`` and ``tools.analyze
--json``.

Fixture packages are real directory trees in tmp_path: the analyzer parses
them exactly like ``dmlc_tpu`` (pure AST — nothing is imported), so a
package literally named ``dmlc_tpu`` exercises the L1/R1 precedence rules
that key on the in-repo paths.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

from tools.analyze.core import run_rules

REPO = Path(__file__).resolve().parent.parent


def write_pkg(root: Path, name: str, files: dict[str, str]) -> Path:
    pkg = root / name
    for rel, src in files.items():
        p = pkg / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        for d in [p.parent, *p.parent.parents]:
            if d == root:
                break
            init = d / "__init__.py"
            if not init.exists():
                init.write_text("")
    return pkg


def analyze(root: Path, name: str, files: dict[str, str]):
    return run_rules(write_pkg(root, name, files)).findings


def rules_of(findings) -> list[str]:
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# A1 — lock-order deadlock
# ---------------------------------------------------------------------------

_CYCLE_A = """
    import threading

    from fx1.b import Beta


    class Alpha:
        def __init__(self, beta: Beta):
            self.beta = beta
            self._lock = threading.Lock()

        def go(self):
            with self._lock:
                self.beta.poke()

        def reenter(self):
            with self._lock:
                return 1
"""

_CYCLE_B = """
    import threading


    class Beta:
        def __init__(self, alpha=None):
            self.alpha = alpha
            self._lock = threading.Lock()

        def poke(self):
            with self._lock:
                return 2

        def prod(self):
            with self._lock:
                self.alpha.reenter()
"""


def test_a1_two_lock_cycle_with_witness(tmp_path):
    findings = analyze(tmp_path, "fx1", {"a.py": _CYCLE_A, "b.py": _CYCLE_B})
    cycles = [f for f in findings if f.rule == "A1" and "cycle" in f.message.lower()
              or f.rule == "A1" and "deadlock candidate" in f.message]
    assert cycles, f"no A1 cycle reported: {[f.message for f in findings]}"
    f = cycles[0]
    assert "fx1.a.Alpha._lock" in f.message and "fx1.b.Beta._lock" in f.message
    # The witness names both acquisition files and the call hops.
    chain_text = " ".join(s.render() for s in f.chain)
    assert "fx1/a.py" in chain_text and "fx1/b.py" in chain_text
    assert "poke" in chain_text and "reenter" in chain_text


def test_a1_consistent_order_is_clean(tmp_path):
    # Same two classes, but Beta never calls back into Alpha under its
    # lock: a one-way Alpha -> Beta edge is a hierarchy, not a cycle.
    clean_b = _CYCLE_B.replace("self.alpha.reenter()", "pass")
    findings = analyze(tmp_path, "fx1", {"a.py": _CYCLE_A, "b.py": clean_b})
    assert [f for f in findings if f.rule == "A1"] == []


def test_a1_nonreentrant_self_deadlock(tmp_path):
    src = """
        import threading


        class Store:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    return 1
    """
    findings = analyze(tmp_path, "fx1r", {"s.py": src})
    self_dead = [f for f in findings if f.rule == "A1" and "self-deadlock" in f.message]
    assert self_dead, [f.message for f in findings]
    # The RLock variant is legal and must be silent.
    findings = analyze(
        tmp_path / "r2", "fx1r",
        {"s.py": src.replace("threading.Lock()", "threading.RLock()")},
    )
    assert [f for f in findings if f.rule == "A1"] == []


# ---------------------------------------------------------------------------
# A2 — interprocedural blocking-under-lock
# ---------------------------------------------------------------------------

_A2_FILES = {
    "a.py": """
        import threading

        from fx2.b import helper


        class Front:
            def __init__(self):
                self._lock = threading.Lock()

            def serve(self):
                with self._lock:
                    return helper()
    """,
    "b.py": """
        from fx2.c import fetch


        def helper():
            return fetch()
    """,
    "c.py": """
        import time


        def fetch():
            time.sleep(1.0)
            return 3
    """,
}


def test_a2_three_module_chain(tmp_path):
    findings = analyze(tmp_path, "fx2", _A2_FILES)
    a2 = [f for f in findings if f.rule == "A2"]
    assert len(a2) == 1, [f.message for f in findings]
    f = a2[0]
    # Anchored at the lock acquisition — where the suppression/fix belongs.
    assert f.path == "fx2/a.py"
    assert "time.sleep" in f.message and "fx2.a.Front._lock" in f.message
    chain_text = " ".join(s.render() for s in f.chain)
    for hop in ("fx2/a.py", "fx2/b.py", "fx2/c.py"):
        assert hop in chain_text, chain_text


def test_a2_suppression_on_the_acquisition_line(tmp_path):
    files = dict(_A2_FILES)
    files["a.py"] = files["a.py"].replace(
        "with self._lock:",
        "with self._lock:  # dmlc-lint: disable=A2 -- fixture: wait is the "
        "critical section by design",
    )
    findings = analyze(tmp_path, "fx2", files)
    assert [f for f in findings if f.rule == "A2"] == []


def test_a2_defers_same_class_chains_to_l1(tmp_path):
    """A chain L1 already follows (same class, file in L1's scope) must NOT
    fire A2 — precedence means one finding never fires from both tools."""
    src = """
        import threading
        import time


        class Gate:
            def __init__(self):
                self._lock = threading.Lock()

            def serve(self):
                with self._lock:
                    self._helper()

            def _helper(self):
                time.sleep(0.5)
    """
    findings = analyze(tmp_path, "dmlc_tpu", {"cluster/g.py": src})
    assert [f for f in findings if f.rule == "A2"] == []
    # ... but the SAME shape outside L1's scope is A2's to report.
    findings = analyze(tmp_path / "other", "otherpkg", {"g.py": src})
    assert len([f for f in findings if f.rule == "A2"]) == 1


# ---------------------------------------------------------------------------
# A3 — deadline/trace propagation
# ---------------------------------------------------------------------------

_A3_FILES = {
    "svc.py": """
        from fx3.util import relay


        class Svc:
            def __init__(self, rpc):
                self.rpc = rpc

            def methods(self):
                return {"svc.echo": self._echo}

            def _echo(self, p):
                return relay(self.rpc, p)
    """,
    "util.py": """
        def relay(rpc, p):
            return rpc.call("dst:1", "other.m", p)
    """,
}


def test_a3_dropped_deadline_kwarg_with_handler_chain(tmp_path):
    findings = analyze(tmp_path, "fx3", _A3_FILES)
    a3 = [f for f in findings if f.rule == "A3"]
    assert len(a3) == 1, [f.message for f in findings]
    f = a3[0]
    assert f.path == "fx3/util.py"  # anchored where timeout= belongs
    assert "svc.echo" in f.message  # ... naming the serving path that hangs
    chain_text = " ".join(s.render() for s in f.chain)
    assert "fx3/svc.py" in chain_text


def test_a3_bounded_call_is_clean(tmp_path):
    files = dict(_A3_FILES)
    files["util.py"] = """
        def relay(rpc, p):
            return rpc.call("dst:1", "other.m", p, timeout=5.0)
    """
    findings = analyze(tmp_path, "fx3", files)
    assert [f for f in findings if f.rule == "A3"] == []


def test_a3_catches_deadline_less_decode_tier_relay(tmp_path):
    # ISSUE 13 fixture: a decode-tier fan-out reached from a served handler
    # must carry the inbound budget — a deadline-less job.decode hop hangs
    # the reassembly barrier on one dead peer.
    files = {
        "svc.py": """
            from fx13.tier import fan_out


            class Ingest:
                def __init__(self, rpc):
                    self.rpc = rpc

                def methods(self):
                    return {"job.predict": self._predict}

                def _predict(self, p):
                    return fan_out(self.rpc, p["blobs"])
        """,
        "tier.py": """
            def fan_out(rpc, blobs):
                return rpc.call("peer:1", "job.decode", {"size": 224, "blobs": blobs})
        """,
    }
    findings = analyze(tmp_path, "fx13", files)
    a3 = [f for f in findings if f.rule == "A3"]
    assert len(a3) == 1, [f.message for f in findings]
    assert a3[0].path == "fx13/tier.py"
    # Bounding the hop clears it.
    files["tier.py"] = """
        def fan_out(rpc, blobs, timeout_s=30.0):
            return rpc.call(
                "peer:1", "job.decode", {"size": 224, "blobs": blobs},
                timeout=timeout_s,
            )
    """
    findings = analyze(tmp_path / "bounded", "fx13", files)
    assert [f for f in findings if f.rule == "A3"] == []


def test_a3_r1_scope_is_not_rereported(tmp_path):
    # Inside dmlc_tpu/cluster/, the bare call is R1's finding, not A3's.
    src = """
        def relay(rpc, p):
            return rpc.call("dst:1", "other.m", p)
    """
    findings = analyze(tmp_path, "dmlc_tpu", {"cluster/util.py": src})
    assert [f for f in findings if f.rule == "A3"] == []


def test_a3_bind_none_clears_ambient_context(tmp_path):
    files = {
        "cluster/deadline.py": """
            def bind(deadline):
                return deadline
        """,
        "handler.py": """
            from fx5.cluster import deadline


            def run(p):
                with deadline.bind(None):
                    return p
        """,
    }
    findings = analyze(tmp_path, "fx5", files)
    a3 = [f for f in findings if f.rule == "A3"]
    assert len(a3) == 1 and "bind(None)" in a3[0].message
    assert a3[0].path == "fx5/handler.py"


# ---------------------------------------------------------------------------
# A4 — RPC frame schema
# ---------------------------------------------------------------------------

_A4_RPC = """
    def _send_frame(sock, obj):
        sock.push(obj)


    def _recv_frame(sock):
        return sock.pop(), None


    def call(sock, method, payload):
        req = {"m": method, "p": payload, "d": 5.0}
        _send_frame(sock, req)
        reply, _ = _recv_frame(sock)
        if not reply.get("ok"):
            raise RuntimeError(reply.get("e"))
        return reply["r"]


    def serve(sock, table):
        req, _ = _recv_frame(sock)
        out = table[req["m"]](req["p"], req.get("d"))
        _send_frame(sock, {"ok": True, "r": out})
"""


def test_a4_frame_field_typo_and_type_conflict(tmp_path):
    files = {
        "rpc.py": _A4_RPC,
        "client.py": """
            from fx4.rpc import _send_frame


            def ping(sock):
                _send_frame(sock, {"m": "ping", "dd": 1.0})


            def slow_ping(sock):
                req = {"m": "ping", "d": "soon"}
                _send_frame(sock, req)
        """,
    }
    findings = analyze(tmp_path, "fx4", files)
    a4 = [f for f in findings if f.rule == "A4"]
    msgs = " | ".join(f.message for f in a4)
    assert any("'dd'" in f.message and "unknown" in f.message for f in a4), msgs
    assert any("'d'" in f.message and "str" in f.message for f in a4), msgs
    assert all(f.path == "fx4/client.py" for f in a4)


def test_a4_consistent_producers_are_clean(tmp_path):
    files = {
        "rpc.py": _A4_RPC,
        "client.py": """
            from fx4.rpc import _send_frame


            def ping(sock):
                _send_frame(sock, {"m": "ping", "d": 1.0})
        """,
    }
    findings = analyze(tmp_path, "fx4", files)
    assert [f for f in findings if f.rule == "A4"] == []


def test_a4_hard_read_of_never_produced_field(tmp_path):
    files = {
        "rpc.py": _A4_RPC,
        "peer.py": """
            from fx4.rpc import _recv_frame


            def drain(sock):
                reply, _ = _recv_frame(sock)
                return reply["trace"]
        """,
    }
    findings = analyze(tmp_path, "fx4", files)
    a4 = [f for f in findings if f.rule == "A4"]
    assert len(a4) == 1 and "'trace'" in a4[0].message, [f.message for f in a4]


# ---------------------------------------------------------------------------
# A5 — donation-after-use
# ---------------------------------------------------------------------------

_A5_ENGINE = """
    import jax


    class Engine:
        def __init__(self):
            self._step = self._build()

        def _build(self):
            def step(state, x):
                return state + x
            return jax.jit(step, donate_argnums=(0,))

        def run(self, state, x):
            out = self._step(state, x)
            return state.sum()
"""


def test_a5_donated_buffer_read_after_call_with_witness(tmp_path):
    findings = analyze(tmp_path, "fxa5", {"eng.py": _A5_ENGINE})
    a5 = [f for f in findings if f.rule == "A5"]
    assert len(a5) == 1, [f.message for f in findings]
    f = a5[0]
    # Anchored at the donating call, naming the donated value and argnum.
    assert f.path == "fxa5/eng.py"
    assert "state" in f.message and "donated" in f.message
    assert "argnum 0" in f.message
    # The witness ends at the read site (the `state.sum()` line).
    assert f.chain, "A5 findings carry a witness chain"
    read_line = next(
        i + 1 for i, ln in enumerate(_A5_ENGINE.splitlines())
        if "state.sum()" in ln
    )
    assert f.chain[-1].line == read_line


def test_a5_rebinding_through_the_donating_call_is_clean(tmp_path):
    # The canonical `state = step(state, ...)` pattern: the donating
    # statement's own target rebinds the name, so nothing stale survives.
    clean = _A5_ENGINE.replace(
        "out = self._step(state, x)\n            return state.sum()",
        "state = self._step(state, x)\n            return state.sum()",
    )
    findings = analyze(tmp_path, "fxa5", {"eng.py": clean})
    assert [f for f in findings if f.rule == "A5"] == []


def test_a5_interprocedural_reassign_kill_is_clean(tmp_path):
    # engine.py's real shape: the donated pools are re-bound by a helper
    # method called after the donating dispatch.
    src = """
        import jax


        class Engine:
            def __init__(self):
                self._step = self._build()

            def _build(self):
                def step(k, x):
                    return k * x
                return jax.jit(step, donate_argnums=(0,))

            def tick(self, x):
                k = self._step(self._k, x)
                self._install(k)
                return self._k

            def _install(self, k):
                self._k = k
    """
    findings = analyze(tmp_path, "fxa5b", {"eng.py": src})
    assert [f for f in findings if f.rule == "A5"] == []


def test_a5_suppression_on_the_donating_call_line(tmp_path):
    files = {"eng.py": _A5_ENGINE.replace(
        "out = self._step(state, x)",
        "out = self._step(state, x)  # dmlc-lint: disable=A5 -- fixture: "
        "state is host-resident here by design",
    )}
    findings = analyze(tmp_path, "fxa5", files)
    assert [f for f in findings if f.rule == "A5"] == []
    # The suppression is USED, so no S2 stale finding either.
    assert [f for f in findings if f.rule == "S2"] == []


# ---------------------------------------------------------------------------
# A6 — recompile hazards (signature census)
# ---------------------------------------------------------------------------

_A6_BOUNDED = """
    from functools import partial

    import jax


    @partial(jax.jit, static_argnums=(1,))
    def run(x, mode):
        return x


    def fwd(x):
        return run(x, 0)


    def bwd(x):
        return run(x, 1)
"""


def test_a6_two_static_signatures_are_clean(tmp_path):
    findings = analyze(tmp_path, "fxa6", {"m.py": _A6_BOUNDED})
    assert [f for f in findings if f.rule == "A6"] == [], \
        [f.message for f in findings]


def test_a6_loop_variable_at_static_position_is_unbounded(tmp_path):
    src = _A6_BOUNDED + """

    def sweep(x):
        for n in range(64):
            run(x, n)
"""
    findings = analyze(tmp_path, "fxa6", {"m.py": src})
    a6 = [f for f in findings if f.rule == "A6"]
    assert len(a6) == 1, [f.message for f in findings]
    assert "unbounded" in a6[0].message
    assert a6[0].chain, "A6 unbounded findings point back at the jit"


# ---------------------------------------------------------------------------
# A7 — host sync reachable from a hot path
# ---------------------------------------------------------------------------

_A7_FILES = {
    "front.py": """
        from fxa7.mid import relay


        def serve_hot(x):
            return relay(x)
    """,
    "mid.py": """
        from fxa7.sink import materialize


        def relay(x):
            return materialize(x)
    """,
    "sink.py": """
        import jax


        def materialize(x):
            return jax.device_get(x)
    """,
}


def test_a7_sync_three_modules_from_hot_path(tmp_path):
    findings = analyze(tmp_path, "fxa7", _A7_FILES)
    a7 = [f for f in findings if f.rule == "A7"]
    assert len(a7) == 1, [f.message for f in findings]
    f = a7[0]
    # Anchored at the sync itself, naming the hot entry point it stalls.
    assert f.path == "fxa7/sink.py"
    assert "serve_hot" in f.message
    chain_text = " ".join(s.render() for s in f.chain)
    assert "fxa7/mid.py" in chain_text


def test_a7_sync_outside_hot_reachability_is_clean(tmp_path):
    files = dict(_A7_FILES)
    files["front.py"] = files["front.py"].replace("serve_hot", "serve_cold")
    findings = analyze(tmp_path, "fxa7", files)
    assert [f for f in findings if f.rule == "A7"] == []


# ---------------------------------------------------------------------------
# A8 — mesh / PartitionSpec consistency
# ---------------------------------------------------------------------------


def test_a8_undeclared_axis_in_shard_map_spec(tmp_path):
    src = """
        from jax.sharding import Mesh, PartitionSpec
        from jax.experimental.shard_map import shard_map


        def build(devs, fn):
            mesh = Mesh(devs, axis_names=("dp", "tp"))
            return shard_map(fn, mesh=mesh, in_specs=(PartitionSpec("dp"),),
                             out_specs=PartitionSpec("mp"))
    """
    findings = analyze(tmp_path, "fxa8", {"m.py": src})
    a8 = [f for f in findings if f.rule == "A8"]
    assert len(a8) == 1, [f.message for f in findings]
    assert "'mp'" in a8[0].message
    assert "dp" in a8[0].message and "tp" in a8[0].message  # declared axes
    chain_text = " ".join(s.render() for s in a8[0].chain)
    assert "mesh" in chain_text.lower()


def test_a8_rank_mismatched_partition_spec(tmp_path):
    src = """
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec
        from jax.experimental.shard_map import shard_map


        def run(devs, fn):
            mesh = Mesh(devs, axis_names=("dp",))
            x = jnp.zeros((4, 8))
            return shard_map(fn, mesh=mesh,
                             in_specs=(PartitionSpec("dp", None, None),),
                             out_specs=PartitionSpec("dp"))(x)
    """
    findings = analyze(tmp_path, "fxa8r", {"m.py": src})
    a8 = [f for f in findings if f.rule == "A8"]
    assert len(a8) == 1, [f.message for f in findings]
    assert "rank" in a8[0].message


def test_a8_declared_axes_and_matching_rank_are_clean(tmp_path):
    src = """
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec
        from jax.experimental.shard_map import shard_map


        def run(devs, fn):
            mesh = Mesh(devs, axis_names=("dp", "tp"))
            x = jnp.zeros((4, 8))
            return shard_map(fn, mesh=mesh,
                             in_specs=(PartitionSpec("dp", "tp"),),
                             out_specs=PartitionSpec("dp"))(x)
    """
    findings = analyze(tmp_path, "fxa8c", {"m.py": src})
    assert [f for f in findings if f.rule == "A8"] == [], \
        [f.message for f in findings]


def test_a8_dead_partition_rules(tmp_path):
    # Rules behind the catch-all and duplicate patterns are dead: first
    # match wins (parallel/sharding.match_partition_rules), so they can
    # never fire — a param the author meant to shard silently replicates.
    src = """
        from jax.sharding import PartitionSpec as P

        RULES = (
            (r"kernel$", P(None, "tp")),
            (r".*", P()),
            (r"bias$", P("tp")),
        )
        DUP_RULES = (
            (r"kernel$", P(None, "tp")),
            (r"kernel$", P("tp", None)),
            (r".*", P()),
        )
    """
    findings = analyze(tmp_path, "fxa8d", {"m.py": src})
    a8 = sorted(
        (f for f in findings if f.rule == "A8"), key=lambda f: f.line
    )
    assert len(a8) == 2, [f.message for f in findings]
    assert "shadowed by catch-all" in a8[0].message
    assert "'bias$'" in a8[0].message
    assert "duplicates entry 0" in a8[1].message


def test_a8_rule_table_without_catchall_and_bad_regex(tmp_path):
    # No terminal catch-all = spec-less params at mesh>1; a non-compiling
    # regex can never match, so its spec is unreachable.
    src = """
        from jax.sharding import PartitionSpec as P

        NO_CATCHALL = (
            (r"kernel$", P(None, "tp")),
            (r"bias$", P("tp")),
        )
        BAD_REGEX = (
            (r"kernel[", P(None, "tp")),
            (r".*", P()),
        )
    """
    findings = analyze(tmp_path, "fxa8n", {"m.py": src})
    a8 = sorted(
        (f for f in findings if f.rule == "A8"), key=lambda f: f.line
    )
    assert len(a8) == 2, [f.message for f in findings]
    assert "no terminal catch-all" in a8[0].message
    assert "spec-less" in a8[0].message.lower()
    assert "does not compile" in a8[1].message


def test_a8_healthy_rule_table_and_non_tables_are_clean(tmp_path):
    # The repo grammar (ordered rules, terminal catch-all) passes clean,
    # and tuples that merely LOOK pair-shaped but are not (str, P(...))
    # throughout are some other data structure — stay silent.
    src = """
        from jax.sharding import PartitionSpec as P

        RULES = (
            (r"(query|key|value)/kernel$", P(None, "tp")),
            (r"out/kernel$", P("tp", None)),
            (r".*", P()),
        )
        NOT_A_TABLE = (
            ("verb", object()),
            ("other", object()),
        )
    """
    findings = analyze(tmp_path, "fxa8h", {"m.py": src})
    assert [f for f in findings if f.rule == "A8"] == [], \
        [f.message for f in findings]


def test_a8_parameter_mesh_stays_silent(tmp_path):
    # The under-approximation contract: a mesh that arrives as a parameter
    # has unknown axes, so nothing is provable and nothing fires.
    src = """
        from jax.sharding import PartitionSpec
        from jax.experimental.shard_map import shard_map


        def build(mesh, fn, axis):
            return shard_map(fn, mesh=mesh, in_specs=(PartitionSpec(axis),),
                             out_specs=PartitionSpec("anything"))
    """
    findings = analyze(tmp_path, "fxa8p", {"m.py": src})
    assert [f for f in findings if f.rule == "A8"] == []


# ---------------------------------------------------------------------------
# A9 — retry-safety (verbs on retried paths must be registered idempotent)
# ---------------------------------------------------------------------------

_A9_FILES = {
    "client.py": """
        from fx9.walk import pull


        class Client:
            def __init__(self, rpc, retry_policy):
                self.rpc = rpc
                self.retry_policy = retry_policy

            def fetch(self, name):
                # the dispatch that reruns when pull() walks to a fallback
                self.rpc.call("m0:1", "job.mutate_state", {"name": name},
                              timeout=5.0)
                return pull(self)
    """,
    "walk.py": """
        def pull(client):
            for i, dest in enumerate(["m0:1", "m1:1"]):
                if i and not client.retry_policy.allow_retry(dest):
                    continue
                return dest
    """,
}


def test_a9_unregistered_verb_on_retry_path(tmp_path):
    findings = analyze(tmp_path, "fx9", _A9_FILES)
    a9 = [f for f in findings if f.rule == "A9"]
    assert len(a9) == 1, [f.message for f in findings]
    f = a9[0]
    assert f.path == "fx9/client.py"  # anchored at the dispatch site
    assert "job.mutate_state" in f.message
    assert "IDEMPOTENT_VERBS" in f.message
    chain_text = " ".join(s.render() for s in f.chain)
    assert "allow_retry" in chain_text  # witness shows WHY it's a retry path


def test_a9_registered_verb_is_clean(tmp_path):
    files = dict(_A9_FILES)
    # sdfs.fetch_chunk is in the real registry (cluster/rpc.py) — the same
    # registry that licenses dmlc-mc's duplicate-delivery injection.
    files["client.py"] = _A9_FILES["client.py"].replace(
        "job.mutate_state", "sdfs.fetch_chunk"
    )
    findings = analyze(tmp_path, "fx9", files)
    assert [f for f in findings if f.rule == "A9"] == []


def test_a9_no_retry_gate_means_no_finding(tmp_path):
    files = dict(_A9_FILES)
    files["walk.py"] = """
        def pull(client):
            return "m0:1"
    """
    findings = analyze(tmp_path, "fx9", files)
    assert [f for f in findings if f.rule == "A9"] == []


# ---------------------------------------------------------------------------
# S2 — stale suppressions (analyzer-owned A-rules)
# ---------------------------------------------------------------------------


def test_s2_stale_a_rule_suppression_fires(tmp_path):
    src = """
        def quiet():
            return 1  # dmlc-lint: disable=A7 -- nothing here ever synced
    """
    findings = analyze(tmp_path, "fxs2", {"m.py": src})
    s2 = [f for f in findings if f.rule == "S2"]
    assert len(s2) == 1, [f.message for f in findings]
    assert "A7" in s2[0].message and "stale" in s2[0].message


def test_s2_used_suppression_is_not_stale(tmp_path):
    files = dict(_A7_FILES)
    files["sink.py"] = files["sink.py"].replace(
        "return jax.device_get(x)",
        "return jax.device_get(x)  # dmlc-lint: disable=A7 -- fixture: "
        "the readback IS the product here",
    )
    findings = analyze(tmp_path, "fxa7", files)
    assert [f for f in findings if f.rule in ("A7", "S2")] == [], \
        [f.message for f in findings]


# ---------------------------------------------------------------------------
# shared JSON schema + the real tree
# ---------------------------------------------------------------------------


def test_json_schema_shared_between_lint_and_analyze(tmp_path):
    pkg = write_pkg(tmp_path, "fx2", _A2_FILES)
    out = tmp_path / "analyze.json"
    r = subprocess.run(
        [sys.executable, "-m", "tools.analyze", str(pkg), "--json", str(out)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 1
    analyze_doc = json.loads(out.read_text())
    assert analyze_doc and analyze_doc[0]["rule"] == "A2"
    assert analyze_doc[0]["chain"], "analyzer findings carry witness chains"

    bad = tmp_path / "dmlc_tpu" / "cluster"
    bad.mkdir(parents=True, exist_ok=True)
    (bad / "wall.py").write_text("import time\nt = time.time()\n")
    lint_out = tmp_path / "lint.json"
    r = subprocess.run(
        [sys.executable, "-m", "tools.lint", str(bad / "wall.py"),
         "--json", str(lint_out)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 1
    lint_doc = json.loads(lint_out.read_text())
    assert lint_doc[0]["rule"] == "D1" and lint_doc[0]["chain"] == []
    # One schema: identical key sets, chain hops carry path/line/desc.
    assert set(lint_doc[0]) == set(analyze_doc[0])
    assert set(analyze_doc[0]["chain"][0]) == {"path", "line", "desc"}


def test_cli_exits_nonzero_per_seeded_fixture(tmp_path):
    """Acceptance: the CLI exits nonzero on each seeded defect, with the
    witness in stdout."""
    seeds = {
        "fx1": ({"a.py": _CYCLE_A, "b.py": _CYCLE_B}, "A1"),
        "fx2": (_A2_FILES, "A2"),
        "fx3": (_A3_FILES, "A3"),
        "fx4": ({"rpc.py": _A4_RPC, "client.py": """
            from fx4.rpc import _send_frame


            def ping(sock):
                _send_frame(sock, {"m": "ping", "dd": 1.0})
        """}, "A4"),
        "fxa5": ({"eng.py": _A5_ENGINE}, "A5"),
        "fxa6": ({"m.py": _A6_BOUNDED + """

    def sweep(x):
        for n in range(64):
            run(x, n)
"""}, "A6"),
        "fxa7": (_A7_FILES, "A7"),
        "fxa8": ({"m.py": """
            from jax.sharding import Mesh, PartitionSpec
            from jax.experimental.shard_map import shard_map


            def build(devs, fn):
                mesh = Mesh(devs, axis_names=("dp", "tp"))
                return shard_map(fn, mesh=mesh,
                                 in_specs=(PartitionSpec("dp"),),
                                 out_specs=PartitionSpec("mp"))
        """}, "A8"),
    }
    for name, (files, rule) in seeds.items():
        pkg = write_pkg(tmp_path / name, name, files)
        r = subprocess.run(
            [sys.executable, "-m", "tools.analyze", str(pkg)],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 1, f"{name}: rc={r.returncode}\n{r.stdout}"
        assert rule in r.stdout, f"{name}:\n{r.stdout}"


def test_repo_analyzes_clean():
    """The acceptance bar tools/ci_check.sh enforces: zero unsuppressed
    findings over dmlc_tpu/ (and every remaining suppression is justified,
    or dmlc-lint's S1 fires on the same files)."""
    r = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "dmlc_tpu"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, f"dmlc-analyze found:\n{r.stdout}"


def test_lock_graph_documents_the_hierarchy():
    """docs/ANALYZE.md's lock hierarchy is generated from this surface —
    pin the load-bearing edges so the doc cannot silently rot."""
    r = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "dmlc_tpu", "--locks"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0
    assert ("dmlc_tpu.scheduler.jobs.JobScheduler._lock -> "
            "dmlc_tpu.cluster.retrypolicy.RetryPolicy._lock") in r.stdout
    assert ("dmlc_tpu.scheduler.jobs.JobScheduler._lock -> "
            "dmlc_tpu.utils.metrics.Counters._lock") in r.stdout


def test_list_rules():
    r = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "--list-rules"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0
    for rule_id in ("A1", "A2", "A3", "A4", "A5", "A6", "A7", "A8", "A9", "S2"):
        assert rule_id in r.stdout


# ---------------------------------------------------------------------------
# the CI findings ratchet (tools/ratchet.py)
# ---------------------------------------------------------------------------


def _ratchet(pkg, baseline, *extra):
    from tools.ratchet import main
    return main(["--package", str(pkg), "--lint-paths", str(pkg),
                 "--baseline", str(baseline), *extra])


def test_ratchet_lifecycle(tmp_path, capsys):
    """missing baseline -> update grandfathers the defect -> clean gate ->
    a NEW finding fails -> fixing a grandfathered one only warns."""
    pkg = write_pkg(tmp_path / "tree", "fxa7", _A7_FILES)
    baseline = tmp_path / "baseline.json"

    assert _ratchet(pkg, baseline) == 2  # no baseline yet
    assert "tools.ratchet --update" in capsys.readouterr().err

    assert _ratchet(pkg, baseline, "--update") == 0
    entries = json.loads(baseline.read_text())["findings"]
    assert any(e["rule"] == "A7" for e in entries)

    assert _ratchet(pkg, baseline) == 0  # grandfathered == green
    assert "grandfathered" in capsys.readouterr().out

    # A new defect (A5 donation-after-use) is NOT in the baseline: gate fails.
    (pkg / "eng.py").write_text(textwrap.dedent(_A5_ENGINE))
    assert _ratchet(pkg, baseline) == 1
    assert "not in baseline" in capsys.readouterr().out

    # Fix everything: stale baseline entries warn (with the shrink command)
    # but never fail the gate.
    (pkg / "eng.py").unlink()
    (pkg / "sink.py").write_text(textwrap.dedent(_A7_FILES["sink.py"]).replace(
        "return jax.device_get(x)", "return x"))
    assert _ratchet(pkg, baseline) == 0
    out = capsys.readouterr().out
    assert "WARNING" in out and "--update" in out


def test_ratchet_mc_findings_gate(tmp_path, capsys):
    """dmlc-mc violations ride the same ratchet: a new one fails, a
    grandfathered one passes, and a static-only run never reports a
    baseline mc entry as gone (it cannot observe mc findings at all)."""
    pkg = write_pkg(tmp_path / "tree", "fxmc", {"m.py": "X = 1\n"})
    baseline = tmp_path / "baseline.json"
    assert _ratchet(pkg, baseline, "--update") == 0
    mc = tmp_path / "mc.json"
    mc.write_text(json.dumps({"results": [], "findings": [{
        "scenario": "generate_ack", "invariant": "exactly-once-prefix",
        "message": "c0 consumed [7], plan was [101]",
        "trace": ["submit:c0", "step", "poll:c0"],
    }]}))
    assert _ratchet(pkg, baseline, "--mc-findings", str(mc)) == 1
    out = capsys.readouterr()
    assert "exactly-once-prefix" in out.out
    assert _ratchet(pkg, baseline, "--mc-findings", str(mc), "--update") == 0
    assert _ratchet(pkg, baseline, "--mc-findings", str(mc)) == 0
    capsys.readouterr()
    # static-only: the grandfathered mc entry must not warn as "gone"
    assert _ratchet(pkg, baseline) == 0
    assert "no longer fires" not in capsys.readouterr().out
    # with an empty mc run it HAS stopped firing: warn toward shrinking
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"results": [], "findings": []}))
    assert _ratchet(pkg, baseline, "--mc-findings", str(empty)) == 0
    assert "no longer fires" in capsys.readouterr().out


def test_ratchet_accepts_committed_repo_baseline():
    """The committed baseline + the real tree = green gate (what
    tools/ci_check.sh step 1 runs)."""
    r = subprocess.run(
        [sys.executable, "-m", "tools.ratchet"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, f"rc={r.returncode}\n{r.stdout}\n{r.stderr}"


def test_analyzer_runtime_budget():
    """A1-A9 over the whole tree stays inside the 4s interactive budget
    (pure AST, no imports — docs/ANALYZE.md). Raised from 3s with the
    session-router tier (scheduler/genrouter.py), same as 2s -> 3s when
    A9 landed: the budget tracks tree size, the analyzer stays pure-AST.
    Timed on this process's CPU clock, best of two walks: the walk is
    single-threaded, and wall time under five other xdist workers measured
    the machine's load, not the analyzer (it failed the driver's run at
    PR 24 that way)."""
    import time

    def walk() -> float:
        t0 = time.process_time()
        run_rules(REPO / "dmlc_tpu")
        return time.process_time() - t0

    assert min(walk(), walk()) < 4.0
