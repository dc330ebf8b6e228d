"""Client pods: the training half of the distributed runtime.

A :class:`ClientPodRunner` serves TRAIN frames against its engine — it
decodes the round globals off the wire, runs the batched client
training (``engine.build_round_batches`` + ``engine.train_clients``) for
exactly the client ids the frame names, and replies with one UPLOAD
frame holding a codec-encoded blob per client.  It is transport-agnostic
(same code serves a loopback queue pair and a TCP socket) and stateless
across rounds: everything a round needs arrives in the frame, so the
fusion pod can re-route any client to any live pod.

Client k homes on pod ``k % n_pods`` (:func:`shard_clients`), but homing
is only a routing default — re-dispatch after a pod death sends the same
ids elsewhere and the trajectory is unchanged, because per-client
training is a deterministic function of (round, client, globals),
independent of grouping.

``python -m repro.dist.pods`` is the TCP subprocess entry: it rebuilds
an engine from a serialized ExperimentSpec (identical by construction to
the fusion pod's) and serves until SHUTDOWN or socket close.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.dist import frames as fr
from repro.dist.transport import PodEndpoint


def shard_clients(client_ids: Sequence[int], n_pods: int) -> List[List[int]]:
    """Home pod assignment: pod j serves [k for k in ids if k % n_pods == j]."""
    out: List[List[int]] = [[] for _ in range(n_pods)]
    for k in client_ids:
        out[int(k) % n_pods].append(int(k))
    return out


class ClientPodRunner:
    """Serves TRAIN frames for one pod over a :class:`PodEndpoint`.

    ``lock`` serializes the jax work across loopback pod threads (one
    process, one device — contention would only interleave compilation);
    TCP pods own their process and pass no lock.  ``kill()`` stops the
    pod abruptly: a round in flight never uploads, heartbeats cease, and
    the fusion pod's liveness tracking must recover — the chaos tests'
    crash injection point.
    """

    def __init__(self, engine, pod: int, endpoint: PodEndpoint, *,
                 heartbeat_s: float = 5.0,
                 lock: Optional[threading.Lock] = None):
        import jax

        self.engine = engine
        self.pod = int(pod)
        self.endpoint = endpoint
        self.heartbeat_s = float(heartbeat_s)
        self.lock = lock if lock is not None else threading.Lock()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        # shape/dtype templates per prototype for decoding wire globals
        self._templates, self._treedefs = [], []
        for net in engine.nets:
            leaves, treedef = jax.tree.flatten(net.init(jax.random.PRNGKey(0)))
            self._templates.append([np.asarray(l) for l in leaves])
            self._treedefs.append(treedef)

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "ClientPodRunner":
        """Run serve + heartbeat as daemon threads (loopback transport)."""
        for target in (self.serve, self._heartbeat_loop):
            th = threading.Thread(target=target, daemon=True)
            th.start()
            self._threads.append(th)
        return self

    def serve_forever(self) -> None:
        """Heartbeat in a thread, serve inline (tcp subprocess entry)."""
        th = threading.Thread(target=self._heartbeat_loop, daemon=True)
        th.start()
        self._threads.append(th)
        self.serve()

    def kill(self) -> None:
        """Abrupt crash: stop serving and heartbeating immediately."""
        self._stop.set()

    @property
    def killed(self) -> bool:
        return self._stop.is_set()

    def _heartbeat_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_s):
            try:
                self.endpoint.send(fr.encode_frame(fr.Frame(
                    kind=fr.HEARTBEAT, meta={"pod": self.pod})))
            except Exception:
                return

    # -- serving ---------------------------------------------------------

    def serve(self) -> None:
        while not self._stop.is_set():
            data = self.endpoint.recv(timeout=0.05)
            if data is None:
                continue
            try:
                frame = fr.decode_frame(data)
            except fr.FrameError:
                continue  # downlink garbage: the deadline re-dispatches
            if frame.kind == fr.SHUTDOWN:
                return
            if frame.kind != fr.TRAIN:
                continue
            reply = self._handle_train(frame)
            # check AFTER training: a pod killed mid-round never uploads
            if self._stop.is_set():
                return
            self.endpoint.send(reply)

    def _handle_train(self, frame: fr.Frame) -> bytes:
        import jax
        import jax.numpy as jnp

        eng = self.engine
        t = int(frame.round)
        ids = [int(k) for k in frame.client_ids]
        codec = fr.get_codec(frame.meta.get("codec", "fp32"))
        fp32 = fr.get_codec("fp32")
        # downlink globals are always fp32: decoding is exact, so the
        # pod trains from bit-identical params
        blobs = fr.unpack_blobs(frame.payload, len(eng.nets))
        globals_ = []
        for p, blob in enumerate(blobs):
            leaves = fp32.decode(blob, self._templates[p])
            globals_.append(jax.tree.unflatten(
                self._treedefs[p], [jnp.asarray(l) for l in leaves]))
        with self.lock:
            batches = eng.build_round_batches(t, np.asarray(ids, np.int64))
            groups = eng.train_clients(t, globals_, batches)
        per_client: Dict[int, bytes] = {}
        for g, rb in zip(groups, batches):
            if rb is None or g.stack is None:
                continue
            flat, _ = jax.tree.flatten(g.stack)
            host = [np.asarray(l) for l in flat]
            for i, k in enumerate(rb.ks):
                per_client[int(k)] = codec.encode([h[i] for h in host])
        reply = fr.Frame(
            kind=fr.UPLOAD, round=t, wave=int(frame.wave), client_ids=ids,
            codec_id=codec.codec_id,
            meta={"pod": self.pod, "req": frame.meta.get("req"),
                  "attempt": int(frame.meta.get("attempt", 0))},
            payload=fr.pack_blobs([per_client[k] for k in ids]))
        return fr.encode_frame(reply)


# ---------------------------------------------------------------------------
# tcp subprocess entry


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(
        description="repro client pod (tcp transport)")
    ap.add_argument("--spec", required=True,
                    help="path of the serialized ExperimentSpec")
    ap.add_argument("--pod", type=int, required=True)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--heartbeat-s", type=float, default=5.0)
    args = ap.parse_args(argv)

    from repro.api.experiment import build_engine
    from repro.api.spec import ExperimentSpec
    from repro.dist.transport import TCPPodEndpoint

    spec = ExperimentSpec.load(args.spec)
    engine = build_engine(spec)
    endpoint = TCPPodEndpoint(args.host, args.port, args.pod)
    try:
        ClientPodRunner(engine, args.pod, endpoint,
                        heartbeat_s=args.heartbeat_s).serve_forever()
    finally:
        endpoint.close()


if __name__ == "__main__":
    main()
