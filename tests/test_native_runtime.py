"""Native C++ runtime: TCPStore rendezvous, bounded queue.

Parity targets: paddle/fluid/distributed/store/tcp_store.cc (store ops
exercised client/server over loopback, like test/collective's store tests),
buffered-reader-style queue.
"""
import os
import threading

import pytest

from paddle_tpu.core.native import (NativeQueue, TCPStore,
                                    TCPStoreServer, load_native)

pytestmark = pytest.mark.skipif(load_native() is None,
                                reason="native toolchain unavailable")


class TestTCPStore:
    def test_set_get_roundtrip(self):
        srv = TCPStoreServer()
        c = TCPStore("127.0.0.1", srv.port)
        c.set("k", b"hello world")
        assert c.get("k") == b"hello world"
        assert c.get("missing") is None
        c.close()
        srv.stop()

    def test_add_counter_and_wait_across_clients(self):
        srv = TCPStoreServer()
        a = TCPStore("127.0.0.1", srv.port)
        b = TCPStore("127.0.0.1", srv.port)
        assert a.add("cnt", 1) == 1
        assert b.add("cnt", 5) == 6
        assert a.add("cnt", -2) == 4

        err = []

        def waiter():
            try:
                b.wait("flag", timeout_s=10.0)
            except Exception as e:     # pragma: no cover
                err.append(e)

        t = threading.Thread(target=waiter)
        t.start()
        a.set("flag", b"1")
        t.join(timeout=10)
        assert not t.is_alive() and not err
        a.close(); b.close(); srv.stop()

    def test_rendezvous_pattern(self):
        """The init_parallel_env bootstrap dance: N ranks register, barrier."""
        srv = TCPStoreServer()
        world = 4
        results = []

        def rank(i):
            c = TCPStore("127.0.0.1", srv.port)
            c.set(f"worker/{i}", f"addr-{i}".encode())
            n = c.add("barrier", 1)
            if n == world:
                c.set("barrier_done", b"1")
            c.wait("barrier_done", timeout_s=10.0)
            peers = [c.get(f"worker/{j}").decode() for j in range(world)]
            results.append((i, peers))
            c.close()

        ts = [threading.Thread(target=rank, args=(i,)) for i in range(world)]
        [t.start() for t in ts]
        [t.join(timeout=15) for t in ts]
        assert len(results) == world
        for _, peers in results:
            assert peers == [f"addr-{j}" for j in range(world)]
        srv.stop()


class TestNativeQueue:
    def test_fifo_and_blocking(self):
        q = NativeQueue(2)
        assert q.put(1) and q.put(2)
        assert not q.put(3, timeout_s=0.1)      # full
        assert q.get() == 1
        assert q.get() == 2
        assert q.get(timeout_s=0.1) is None     # empty
        q.free()

    def test_producer_consumer(self):
        q = NativeQueue(4)
        got = []

        def consumer():
            while True:
                t = q.get(timeout_s=5.0)
                if t is None or t == 999:
                    break
                got.append(t)

        th = threading.Thread(target=consumer)
        th.start()
        for i in range(1, 51):
            assert q.put(i, timeout_s=5.0)
        q.put(999, timeout_s=5.0)
        th.join(timeout=10)
        assert got == list(range(1, 51))
        q.free()
