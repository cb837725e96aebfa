"""RPC fabric tests: deterministic sim semantics + real TCP loopback."""

import socket
import sys
import threading

import pytest

from dmlc_tpu.cluster import rpc as rpc_mod
from dmlc_tpu.cluster.auth import FrameAuth
from dmlc_tpu.cluster.rpc import (
    RpcError,
    RpcUnreachable,
    SimRpcNetwork,
    TcpRpc,
    TcpRpcServer,
)
from dmlc_tpu.utils.tracing import traced_methods, tracer


def echo_methods():
    return {
        "echo": lambda p: {"echo": p},
        "boom": lambda p: (_ for _ in ()).throw(ValueError("kapow")),
        "blob": lambda p: {"data": p["data"] + b"!"},
    }


class TestSim:
    def test_roundtrip(self):
        net = SimRpcNetwork()
        net.serve("a", echo_methods())
        assert net.client("b").call("a", "echo", {"x": 1}) == {"echo": {"x": 1}}

    def test_unknown_method(self):
        net = SimRpcNetwork()
        net.serve("a", echo_methods())
        with pytest.raises(RpcError):
            net.client("b").call("a", "nope", {})

    def test_crash_and_partition(self):
        net = SimRpcNetwork()
        net.serve("a", echo_methods())
        c = net.client("b")
        net.crash("a")
        with pytest.raises(RpcUnreachable):
            c.call("a", "echo", {})
        net.restart("a")
        assert c.call("a", "echo", {}) == {"echo": {}}
        net.partition("a", "b")
        with pytest.raises(RpcUnreachable):
            c.call("a", "echo", {})
        net.heal("a", "b")
        assert c.call("a", "echo", {}) == {"echo": {}}


class TestTcp:
    def test_roundtrip_and_errors(self):
        server = TcpRpcServer("127.0.0.1", 0, echo_methods())
        try:
            rpc = TcpRpc()
            assert rpc.call(server.address, "echo", {"k": "v"}) == {"echo": {"k": "v"}}
            # Binary payloads survive msgpack framing intact.
            blob = bytes(range(256)) * 100
            assert rpc.call(server.address, "blob", {"data": blob})["data"] == blob + b"!"
            # Remote method error surfaces as RpcError with the message.
            with pytest.raises(RpcError, match="kapow"):
                rpc.call(server.address, "boom", {})
            with pytest.raises(RpcError):
                rpc.call(server.address, "nope", {})
        finally:
            server.close()

    def test_unreachable(self):
        rpc = TcpRpc()
        with pytest.raises(RpcUnreachable):
            rpc.call("127.0.0.1:1", "echo", {}, timeout=0.5)

    def test_authenticated_roundtrip(self):
        from dmlc_tpu.cluster.auth import FrameAuth

        server = TcpRpcServer("127.0.0.1", 0, echo_methods(), auth=FrameAuth("k1"))
        try:
            rpc = TcpRpc(auth=FrameAuth("k1"))
            assert rpc.call(server.address, "echo", {"k": "v"}) == {"echo": {"k": "v"}}
            with pytest.raises(RpcError, match="kapow"):
                rpc.call(server.address, "boom", {})
        finally:
            server.close()

    def test_unauthenticated_frames_rejected(self):
        from dmlc_tpu.cluster.auth import FrameAuth

        server = TcpRpcServer("127.0.0.1", 0, echo_methods(), auth=FrameAuth("k1"))
        try:
            # No key: the server drops the connection without a reply — the
            # caller learns nothing (no error oracle), and the method never
            # ran.
            with pytest.raises(RpcUnreachable):
                TcpRpc().call(server.address, "echo", {}, timeout=2.0)
            # Wrong key: same silence.
            with pytest.raises(RpcUnreachable):
                TcpRpc(auth=FrameAuth("other")).call(server.address, "echo", {}, timeout=2.0)
            # The server survives both and still answers a keyed caller.
            rpc = TcpRpc(auth=FrameAuth("k1"))
            assert rpc.call(server.address, "echo", {}) == {"echo": {}}
        finally:
            server.close()

    def test_keyed_client_rejects_unkeyed_server(self):
        from dmlc_tpu.cluster.auth import FrameAuth

        server = TcpRpcServer("127.0.0.1", 0, echo_methods())  # no auth
        try:
            # Mutual: a keyed member never completes a call against an
            # unkeyed (spoofed) server — either the server drops the sealed
            # frame as malformed (this path) or, if it answered, the untagged
            # reply would fail the client's check.
            with pytest.raises(RpcUnreachable):
                TcpRpc(auth=FrameAuth("k1")).call(server.address, "echo", {}, timeout=2.0)
        finally:
            server.close()

    def test_server_survives_malformed_client(self):
        server = TcpRpcServer("127.0.0.1", 0, echo_methods())
        try:
            import socket

            host, _, port = server.address.rpartition(":")
            with socket.create_connection((host, int(port)), timeout=1) as s:
                s.sendall(b"\x00\x00\x00\x04junk")  # valid frame, invalid msgpack
            rpc = TcpRpc()
            assert rpc.call(server.address, "echo", {}) == {"echo": {}}
        finally:
            server.close()


def _served(log: list, tag=None) -> dict:
    """Methods that record the serving thread (one a connection) of each call."""

    def echo(p):
        log.append(threading.get_ident())
        return {"echo": p, "tag": tag}

    return traced_methods({"echo": echo, "boom": echo_methods()["boom"]})


@pytest.fixture
def spans():
    was = tracer.enabled
    tracer.reset()
    tracer.enabled = True
    try:
        yield tracer
    finally:
        tracer.enabled = was
        tracer.reset()


class TestTcpReuse:
    @pytest.mark.parametrize("key", [None, "k1"], ids=["open", "keyed"])
    def test_sequential_calls_dial_once(self, spans, key):
        auth = (lambda: FrameAuth(key)) if key else (lambda: None)
        served: list = []
        server = TcpRpcServer("127.0.0.1", 0, _served(served), auth=auth())
        rpc = TcpRpc(auth=auth())
        try:
            for i in range(5):
                assert rpc.call(server.address, "echo", {"i": i})["echo"] == {"i": i}
                # A remote error is a whole reply frame: the socket is kept.
                with pytest.raises(RpcError, match="kapow"):
                    rpc.call(server.address, "boom", {})
            assert len(set(served)) == 1, "one connection serves every call"
            fresh = [e["attrs"]["fresh"] for e in spans.events_wire()
                     if e["name"] in ("rpc/echo", "rpc/boom")]
            assert fresh == [1] + [0] * 9
        finally:
            rpc.close()
            server.close()

    def test_concurrent_callers_get_their_own_replies(self):
        served: list = []
        server = TcpRpcServer("127.0.0.1", 0, _served(served))
        rpc = TcpRpc()
        wrong: list = []

        def caller(c: int) -> None:
            for j in range(50):
                got = rpc.call(server.address, "echo", {"c": c, "j": j}, timeout=30.0)
                if got["echo"] != {"c": c, "j": j}:
                    wrong.append((c, j, got))

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=caller, args=(c,)) for c in range(32)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60.0)
            assert not any(t.is_alive() for t in threads)
            # A connection per caller at most, never one per call.
            assert 1 <= len(rpc._idle[server.address]) <= 32
        finally:
            sys.setswitchinterval(interval)
            rpc.close()
            server.close()
        assert wrong == []
        assert len(served) == 32 * 50
        assert len(set(served)) <= 32

    def test_late_reply_is_never_read_as_the_next(self, spans):
        release, finished = threading.Event(), threading.Event()
        ran: list = []

        def slow(p):
            ran.append(p)
            release.wait(10.0)
            finished.set()
            return {"late": True}

        methods = _served([])
        methods.update(traced_methods({"slow": slow}))
        server = TcpRpcServer("127.0.0.1", 0, methods)
        rpc = TcpRpc()
        try:
            assert rpc.call(server.address, "echo", {"n": 0})["echo"] == {"n": 0}
            with pytest.raises(RpcUnreachable):
                rpc.call(server.address, "slow", {}, timeout=0.3)
            # The slow handler replies while the next call waits: on the
            # timed-out socket, that reply would be read as the next one's.
            timer = threading.Timer(0.2, release.set)
            timer.start()
            assert rpc.call(server.address, "echo", {"n": 1})["echo"] == {"n": 1}
            timer.join()
            assert finished.wait(10.0)
            assert ran == [{}], "the request was sent once"
            fresh = [e["attrs"]["fresh"] for e in spans.events_wire()
                     if e["name"] == "rpc/echo"]
            assert fresh == [1, 1], "the timed-out socket was closed, not kept"
        finally:
            rpc.close()
            server.close()

    def test_closed_server_answers_nothing_on_a_kept_connection(self):
        served: list = []
        server = TcpRpcServer("127.0.0.1", 0, _served(served))
        host, _, port = server.address.rpartition(":")
        rpc = TcpRpc()
        raw = socket.create_connection((host, int(port)), timeout=2.0)
        try:
            rpc.call(server.address, "echo", {})
            kept = rpc._idle[server.address][-1]
            rpc_mod._send_frame(raw, {"m": "echo", "p": {}, "d": 2.0})
            assert rpc_mod._recv_frame(raw)[0]["ok"]
            server.close()
            with pytest.raises(RpcUnreachable):
                rpc.call(server.address, "echo", {}, timeout=2.0)
            assert kept.fileno() == -1, "the kept socket read EOF at its peek"
            # A frame sent down a connection the server kept is not answered.
            try:
                rpc_mod._send_frame(raw, {"m": "echo", "p": {}, "d": 2.0})
                assert raw.recv(1) == b""
            except OSError:
                pass  # reset: equally unanswered
            assert len(served) == 2, "nothing ran after close"
        finally:
            raw.close()
            rpc.close()
            server.close()

    def test_call_in_flight_at_close_loses_its_reply(self):
        entered, release = threading.Event(), threading.Event()
        ran: list = []

        def slow(p):
            ran.append(p)
            entered.set()
            release.wait(10.0)
            return {"late": True}

        server = TcpRpcServer("127.0.0.1", 0, {"slow": slow})
        rpc = TcpRpc()
        errs: list = []

        def caller():
            try:
                rpc.call(server.address, "slow", {"n": 1}, timeout=10.0)
            except RpcError as e:
                errs.append(e)

        t = threading.Thread(target=caller)
        try:
            t.start()
            assert entered.wait(10.0)
            server.close()
            t.join(timeout=10.0)
            assert not t.is_alive()
        finally:
            release.set()
            rpc.close()
            server.close()
        assert len(errs) == 1 and isinstance(errs[0], RpcUnreachable)
        assert ran == [{"n": 1}], "the request was sent once"
        assert rpc._idle.get(server.address) is None

    def test_restarted_server_is_reached_by_a_fresh_dial(self):
        first = TcpRpcServer("127.0.0.1", 0, _served([], tag="first"))
        host, _, port = first.address.rpartition(":")
        rpc = TcpRpc()
        try:
            assert rpc.call(first.address, "echo", {})["tag"] == "first"
            stale = rpc._idle[first.address][-1]
            first.close()
            second = TcpRpcServer(host, int(port), _served([], tag="second"))
            try:
                assert rpc.call(second.address, "echo", {})["tag"] == "second"
                assert stale.fileno() == -1, "the stale socket failed its peek and was closed"
                assert len(rpc._idle[second.address]) == 1
            finally:
                second.close()
        finally:
            rpc.close()
            first.close()
