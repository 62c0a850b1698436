"""Host-bridge ceiling: loopback pump through the net layer.

≙ the reference's ASIO thread at wire speed (asio/epoll.c:207-230) —
this measures the equivalent ceiling of THIS runtime's host plane:
C loopback TCP connections ping-ponging M messages each through
host-cohort actors (socket → bridge → host dispatch → socket). The
result is the msgs/s bound a chatty-net program hits BEFORE the device
ever matters (the host plane is single-threaded Python by design).

Usage: python profiling/_bridge_pump.py [clients] [msgs_per_client]
"""

import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import I32, Runtime, RuntimeOptions, actor, behaviour


@actor
class PumpServer:
    HOST = True
    n_msgs: I32
    n_closed: I32
    n_conns: I32

    @behaviour
    def on_accept(self, st, conn: I32):
        return st

    @behaviour
    def on_data(self, st, conn: I32, data: I32, n: I32):
        payload = self.rt.heap.unbox(data)
        self.rt.net.send(conn, payload)          # echo
        return {**st, "n_msgs": st["n_msgs"] + 1}

    @behaviour
    def on_closed(self, st, conn: I32):
        # All clients hung up -> measurement over (the listener holds
        # the runtime alive otherwise and run() would spin out its
        # step budget -- the round-5 mis-measurement).
        done = st["n_closed"] + 1
        self.exit(0, when=done >= st["n_conns"])
        return {**st, "n_closed": done}


def make_client(m_msgs: int):
    @actor
    class PumpClient:
        HOST = True
        conn: I32
        sent: I32

        @behaviour
        def on_connect(self, st, conn: I32, err: I32):
            assert err == 0, err
            self.rt.net.send(conn, b"x" * 64)
            return {**st, "conn": conn, "sent": 1}

        @behaviour
        def on_data(self, st, conn: I32, data: I32, n: I32):
            self.rt.heap.unbox(data)
            if st["sent"] >= m_msgs:
                self.rt.net.close(conn)
                return st
            self.rt.net.send(conn, b"x" * 64)
            return {**st, "sent": st["sent"] + 1}

        @behaviour
        def on_closed(self, st, conn: I32):
            return st

    return PumpClient


def main(clients: int, m_msgs: int):
    cli_t = make_client(m_msgs)
    rt = Runtime(RuntimeOptions(mailbox_cap=32, batch=8, max_sends=2,
                                msg_words=4, inject_slots=256))
    rt.declare(PumpServer, 1).declare(cli_t, clients).start()
    net = rt.attach_net()
    srv = rt.spawn(PumpServer, n_conns=clients)
    lid = net.listen_tcp("127.0.0.1", 0, srv,
                         on_accept=PumpServer.on_accept,
                         on_data=PumpServer.on_data,
                         on_closed=PumpServer.on_closed)
    port = net.listen_port(lid)
    t0 = time.perf_counter()
    for _ in range(clients):
        c = rt.spawn(cli_t)
        net.connect_tcp("127.0.0.1", port, c,
                        on_connect=cli_t.on_connect,
                        on_data=cli_t.on_data,
                        on_closed=cli_t.on_closed)
    rt.run(max_steps=clients * m_msgs * 40 + 4000)
    dt = time.perf_counter() - t0
    served = int(rt.state_of(srv)["n_msgs"])
    # One "message" = one socket payload crossing the bridge into a
    # host-actor dispatch; count both directions.
    total = served * 2
    print(f"clients={clients} msgs/conn={m_msgs} served={served} "
          f"elapsed={dt:.2f}s bridge_msgs_per_sec={total / dt:,.0f}",
          flush=True)
    net.close_all()
    rt.stop()


if __name__ == "__main__":
    c = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    m = int(sys.argv[2]) if len(sys.argv) > 2 else 500
    main(c, m)
