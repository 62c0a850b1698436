"""TCP echo server (≙ examples/echo + packages/net usage): run, then
`nc localhost <port>` — lines come back upper-cased. Ctrl-C to stop."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import I32, Runtime, RuntimeOptions, actor, behaviour


@actor
class Echo:
    HOST = True
    n_conns: I32

    @behaviour
    def on_accept(self, st, conn: I32):
        print(f"connection {conn} accepted")
        return {**st, "n_conns": st["n_conns"] + 1}

    @behaviour
    def on_data(self, st, conn: I32, data: I32, n: I32):
        payload = self.rt.heap.unbox(data)
        self.rt.net.send(conn, payload.upper())
        return st

    @behaviour
    def on_closed(self, st, conn: I32):
        print(f"connection {conn} closed")
        return st


def main():
    port = int(sys.argv[1]) if len(sys.argv) > 1 else 0
    rt = Runtime(RuntimeOptions(msg_words=4, inject_slots=64))
    rt.declare(Echo, 1).start()
    net = rt.attach_net()
    srv = rt.spawn(Echo)
    lid = net.listen_tcp("127.0.0.1", port, srv,
                         on_accept=Echo.on_accept, on_data=Echo.on_data,
                         on_closed=Echo.on_closed)
    print(f"echo listening on 127.0.0.1:{net.listen_port(lid)}")
    try:
        rt.run()
    except KeyboardInterrupt:
        pass
    finally:
        net.close_all()
        rt.stop()


if __name__ == "__main__":
    main()
