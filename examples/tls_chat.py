"""TLS chat server — the round-trip showcase: one program using the
net layer with TLS, iso payload handles, device actors for fan-out
bookkeeping, and host actors for I/O (≙ the reference's chat-server
idiom: a TCPListener whose notify spawns per-connection actors,
upgraded with the SSL filter layer).

Architecture:
  - `Hub` (HOST): owns the listener; on_accept registers the client,
    on_data broadcasts the line to every connected client (payloads
    ride the HostHeap), on_closed unregisters.
  - `Stats` (device): a device actor counting messages/joins — the
    device world observing host traffic (every broadcast pings it).

Run plainly and it drives itself: spawns the server on an ephemeral
loopback port, connects three TLS clients, has them chat, and prints
the transcript. With `--port` it serves the ephemeral port until
Ctrl-C (connect with: openssl s_client -connect 127.0.0.1:<printed>).
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import I32, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu.net.tls import (TLSClientConfig,  # noqa: E402
                               TLSServerConfig)


def selfsigned_cert():
    """Generate a throwaway localhost cert (demo only)."""
    import datetime

    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import rsa
    from cryptography.x509.oid import NameOID
    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    name = x509.Name(
        [x509.NameAttribute(NameOID.COMMON_NAME, "localhost")])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder()
            .subject_name(name).issuer_name(name)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(minutes=5))
            .not_valid_after(now + datetime.timedelta(days=1))
            .add_extension(x509.SubjectAlternativeName(
                [x509.DNSName("localhost")]), critical=False)
            .sign(key, hashes.SHA256()))
    d = tempfile.mkdtemp(prefix="tlschat")
    cf, kf = os.path.join(d, "cert.pem"), os.path.join(d, "key.pem")
    with open(cf, "wb") as f:
        f.write(cert.public_bytes(serialization.Encoding.PEM))
    with open(kf, "wb") as f:
        f.write(key.private_bytes(
            serialization.Encoding.PEM,
            serialization.PrivateFormat.TraditionalOpenSSL,
            serialization.NoEncryption()))
    return cf, kf


@actor
class Stats:
    """Device-side bookkeeping: the host hub pings it per event."""
    joins: I32
    lines: I32

    @behaviour
    def joined(self, st, _: I32):
        return {**st, "joins": st["joins"] + 1}

    @behaviour
    def chatted(self, st, _: I32):
        return {**st, "lines": st["lines"] + 1}


@actor
class Hub:
    HOST = True
    stats: I32
    n: I32

    @behaviour
    def on_accept(self, st, cid: I32):
        MEMBERS.add(int(cid))
        self.rt.net.send(int(cid), b"* welcome to tls-chat\n")
        self.send(st["stats"], Stats.joined, 0)
        return {**st, "n": st["n"] + 1}

    @behaviour
    def on_data(self, st, cid: I32, h: I32, n: I32):
        line = self.rt.heap.unbox(int(h))          # iso payload: ours now
        TRANSCRIPT.append((int(cid), bytes(line)))
        out = b"[%d] " % int(cid) + bytes(line)
        for m in list(MEMBERS):
            try:
                self.rt.net.send(m, out)           # encrypted per member
            except KeyError:
                MEMBERS.discard(m)
        self.send(st["stats"], Stats.chatted, 0)
        return st

    @behaviour
    def on_closed(self, st, cid: I32):
        MEMBERS.discard(int(cid))
        return st


@actor
class Client:
    HOST = True
    got: I32

    @behaviour
    def on_connect(self, st, cid: I32, err: I32):
        return st

    @behaviour
    def on_data(self, st, cid: I32, h: I32, n: I32):
        RECEIVED.setdefault(int(cid), []).append(
            self.rt.heap.unbox(int(h)))
        return {**st, "got": st["got"] + 1}

    @behaviour
    def on_closed(self, st, cid: I32):
        return st


MEMBERS = set()
TRANSCRIPT = []
RECEIVED = {}


def main():
    certfile, keyfile = selfsigned_cert()
    rt = Runtime(RuntimeOptions(mailbox_cap=16, batch=4, max_sends=1,
                                msg_words=3, inject_slots=64))
    rt.declare(Hub, 1).declare(Client, 4).declare(Stats, 1).start()
    stats = rt.spawn(Stats)
    hub = rt.spawn(Hub, stats=int(stats))
    net = rt.attach_net()
    lid = net.listen_tcp("127.0.0.1", 0, hub,
                         on_accept=Hub.on_accept, on_data=Hub.on_data,
                         on_closed=Hub.on_closed,
                         tls=TLSServerConfig(certfile, keyfile))
    port = net.listen_port(lid)
    print(f"tls-chat listening on 127.0.0.1:{port}")

    try:
        if "--port" in sys.argv:
            # Serve mode: stay up until Ctrl-C; connect with
            #   openssl s_client -connect 127.0.0.1:<port>
            rt.add_noisy()             # a server is never "done"
            try:
                rt.run()
            except KeyboardInterrupt:
                print("\nshutting down")
            return

        # Scripted session: three TLS clients join and chat.
        ccfg = TLSClientConfig("localhost", cafile=certfile)
        cids = []
        for _ in range(3):
            c = rt.spawn(Client)
            cids.append(net.connect_tcp("127.0.0.1", port, c,
                                        on_connect=Client.on_connect,
                                        on_data=Client.on_data,
                                        on_closed=Client.on_closed,
                                        tls=ccfg))
        net.send(cids[0], b"hello from alice\n")
        net.send(cids[1], b"hi, bob here\n")
        net.send(cids[2], b"carol joining in\n")

        def lines_seen():
            # TLS coalesces records: count NEWLINES, not deliveries.
            return sum(chunk.count(b"\n")
                       for v in RECEIVED.values() for chunk in v)

        for _ in range(4000):
            rt.run(max_steps=4)
            if len(TRANSCRIPT) >= 3 and lines_seen() >= 12:
                break                  # (welcome + 3 lines) × 3 members
        st = rt.state_of(stats)
        print(f"joins={st['joins']} lines={st['lines']} "
              f"members={len(MEMBERS)}")
        for cid, line in TRANSCRIPT:
            print(f"  [{cid}] {line.decode().strip()}")
        assert st["joins"] == 3 and st["lines"] == 3
        assert lines_seen() >= 12
        print("chat session over (all lines broadcast over TLS)")
    finally:
        net.close_all()
        rt.stop()


if __name__ == "__main__":
    main()
