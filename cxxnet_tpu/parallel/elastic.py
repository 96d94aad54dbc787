"""Elastic multi-host training: preemption-tolerant data-parallel workers
with bitwise-equal recovery (doc/fault_tolerance.md "Multi-host recovery").

The reference's scale-out story was a distributed parameter server
(mshadow-ps ``Push``/``Pull``, ``src/nnet/nnet_ps_server.cpp``); this
module lands that story on preemptible fleets, where the interesting
property is not peak bandwidth but *survivability*: a killed host must
mean restore-last-good and rejoin — never a dead run — and the recovered
run must end **bitwise equal** to a fault-free one.

Design (one deliberate invariant per layer):

* **Input sharding** — every host reads the same global sample stream
  but materializes only instances ``i % hosts == rank`` through the
  ``nworker`` pool, whose per-instance RNG keys on the GLOBAL
  epoch-absolute index (``io/iter_augment.py``).  The PR 5 invariant,
  promoted from threads to hosts: interleaving the per-host streams
  reconstructs the 1-host stream bitwise at any host count.
* **Step math** — each optimizer step's global batch is split into
  ``shards`` fixed micro-shards (``dist.shards``, a multiple of the
  host count).  A host computes gradient contributions for the shards
  it owns (shard ``s`` → host ``s % hosts``), pushes them to the
  coordinator, pulls the full set back, and every host folds the SAME
  transported bytes in ascending shard order before one local optimizer
  apply.  Because the fold never mentions the host count, params stay
  bitwise-replicated with no broadcast — and a 4-host run equals a
  1-host run equals a recovered run, byte for byte.  (This is the
  parameter-server push/pull shape, not an XLA collective: on a TPU
  fleet the same exchange rides ``jax.distributed`` + DCN allreduce;
  over the chaos-drill harness it rides the coordinator socket so that
  a killed process is an ordinary, drillable event.)
* **Coordination point** — ``TrainSupervisor`` + ``AsyncCheckpointer``
  (PR 1/3) already own restore-last-good; :class:`ElasticSupervisor`
  subclasses the supervisor so that every gate-accepted save is a
  cross-host barrier (rank 0 writes, everyone fences), recovery
  rendezvouses the next membership *generation* before restoring, and a
  post-restore CRC barrier proves all hosts resumed from identical
  bytes.
* **Membership** — workers heartbeat an :class:`ElasticCoordinator`
  (a thread in the launcher process, so no worker death can take it
  down).  A missed heartbeat, a dead socket, or a reported fault bumps
  the generation and aborts in-flight collectives: blocked peers get a
  rollback notice and raise ``faults.HostLossError`` — a RECOVERABLE
  fault — while the launcher respawns the lost rank, which rejoins the
  rendezvous at the restored step.

The whole story is drillable: ``train.fault_plan=host_loss=N[:rank]``
kills a worker mid-step, ``partition=N:secs`` takes one off the network
(``runtime/faults.py``), and ``tests/test_elastic.py`` proves the
bitwise-equal-recovery headline at 1, 2 and 4 hosts.
"""

from __future__ import annotations

import itertools
import json
import os
import socket
import struct
import threading
import time
import zlib
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..runtime import faults
from ..runtime.supervisor import SupervisorConfig, TrainSupervisor

# --- wire protocol ---------------------------------------------------------
#
# One frame = MAGIC + u32 header length + JSON header + raw buffers
# (lengths in the header's "blens").  Tensors travel as raw bytes —
# floats never round-trip through text, which is what lets every host
# fold the identical gradient bytes.

_MAGIC = b'CXEL'


def send_frame(sock: socket.socket, hdr: dict,
               bufs: Tuple[bytes, ...] = ()) -> None:
    hdr = dict(hdr)
    hdr['blens'] = [len(b) for b in bufs]
    payload = json.dumps(hdr).encode()
    # header in one send, then each buffer as-is: the per-step gradient
    # payload is never copied into a second staging buffer
    sock.sendall(_MAGIC + struct.pack('<I', len(payload)) + payload)
    for b in bufs:
        sock.sendall(b)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            # transport speaks OSError-family; the client/coordinator
            # map it onto the typed taxonomy at the boundary
            # lint: allow(fault-taxonomy): transport-layer OSError contract
            raise ConnectionError('elastic peer closed the connection')
        buf += chunk
    return bytes(buf)


def recv_frame(sock: socket.socket) -> Tuple[dict, List[bytes]]:
    magic = _recv_exact(sock, 4)
    if magic != _MAGIC:
        # a garbled frame IS a broken connection (same contract as above)
        # lint: allow(fault-taxonomy): transport-layer OSError contract
        raise ConnectionError(f'elastic protocol: bad magic {magic!r}')
    (hlen,) = struct.unpack('<I', _recv_exact(sock, 4))
    hdr = json.loads(_recv_exact(sock, hlen).decode())
    bufs = [_recv_exact(sock, n) for n in hdr.get('blens', [])]
    return hdr, bufs


def params_crc(params) -> int:
    """crc32 over every param leaf's bytes, in pytree order — the cheap
    cross-host "did we all restore the same model" probe (the elastic
    analog of ``trainer.check_weight_consistency``)."""
    import jax
    crc = 0
    for leaf in jax.tree.leaves(params):
        crc = zlib.crc32(np.ascontiguousarray(np.asarray(leaf)).tobytes(),
                         crc)
    return crc


# --- coordinator -----------------------------------------------------------


class _Member:
    """One registered worker, from the coordinator's side."""

    def __init__(self, rank: int, conn: socket.socket):
        self.rank = rank
        self.conn = conn
        self.last_hb = time.monotonic()
        self.gen = -1            # generation this member last rendezvoused


class ElasticCoordinator:
    """Membership + collectives service for one elastic training job.

    Runs in the LAUNCHER process (threads named ``cxxnet-elastic-*``) so
    no worker preemption can take it down.  All state transitions happen
    under ``_cond``; blocked request handlers wait on it and re-check
    the generation — a membership change releases every waiter with a
    rollback notice instead of leaving it parked on a dead collective.
    """

    def __init__(self, nhosts: int, heartbeat_timeout: float = 6.0,
                 on_host_lost: Optional[Callable[[int], None]] = None,
                 failure_log: Optional[faults.FailureLog] = None):
        if nhosts < 1:
            raise ValueError(f'nhosts must be >= 1, got {nhosts}')
        self.nhosts = int(nhosts)
        self.heartbeat_timeout = float(heartbeat_timeout)
        self.on_host_lost = on_host_lost
        # `is None`, not truthiness: an EMPTY FailureLog is falsy
        self.failure_log = (faults.global_failure_log()
                            if failure_log is None else failure_log)
        self._cond = threading.Condition()
        self._gen = 0                 # guarded-by: _cond
        self._stop = False            # guarded-by: _cond
        self._hello: Dict[int, _Member] = {}     # guarded-by: _cond
        self._members: Dict[int, _Member] = {}   # guarded-by: _cond
        self._welcomed_gen = -1       # guarded-by: _cond
        self._contrib: Dict[int, Tuple[dict, List[bytes]]] = {} \
            # guarded-by: _cond
        self._result = None           # guarded-by: _cond
        self._result_step = -1        # guarded-by: _cond
        self._result_left = 0        # guarded-by: _cond
        self._barriers: Dict[str, Dict[int, object]] = {} \
            # guarded-by: _cond
        self._released: Dict[str, Tuple[int, int, Dict[int, object]]] = {} \
            # guarded-by: _cond
        self._events: List[str] = []  # guarded-by: _cond
        self._threads: List[threading.Thread] = []  # guarded-by: _cond
        self._conns: List[socket.socket] = []       # guarded-by: _cond
        self._srv: Optional[socket.socket] = None
        self.address = ''

    # -- lifecycle --
    def start(self) -> str:
        """Bind, start the accept + monitor threads, return host:port."""
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(('127.0.0.1', 0))
        srv.listen(self.nhosts * 4)
        # closing a socket does NOT reliably wake a thread blocked in
        # accept(); poll with a timeout so stop() is prompt
        srv.settimeout(0.5)
        self._srv = srv
        host, port = srv.getsockname()
        self.address = f'{host}:{port}'
        for name, fn in (('cxxnet-elastic-accept', self._accept_loop),
                         ('cxxnet-elastic-mon', self._monitor_loop)):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            with self._cond:
                self._threads.append(t)
        return self.address

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            conns = list(self._conns)
            threads = list(self._threads)
            self._cond.notify_all()
        if self._srv is not None:
            try:
                self._srv.close()
            except OSError:
                pass
        for c in conns:
            try:
                c.close()
            except OSError:
                pass
        for t in threads:
            t.join(timeout=5.0)

    def events(self) -> List[str]:
        with self._cond:
            return list(self._events)

    def generation(self) -> int:
        with self._cond:
            return self._gen

    # -- internals --
    def _accept_loop(self) -> None:
        while True:
            try:
                conn, _addr = self._srv.accept()
            except socket.timeout:
                with self._cond:
                    if self._stop:
                        return
                continue
            except OSError:
                return                       # stop() closed the socket
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 name='cxxnet-elastic-conn', daemon=True)
            with self._cond:
                if self._stop:
                    conn.close()
                    return
                self._conns.append(conn)
                self._threads.append(t)
            t.start()

    def _monitor_loop(self) -> None:
        while True:
            with self._cond:
                if self._stop:
                    return
                now = time.monotonic()
                stale = [m for m in self._members.values()
                         if m.gen == self._gen
                         and now - m.last_hb > self.heartbeat_timeout]
                for m in stale:
                    self._lost_locked(m.rank, 'missed heartbeats')
                self._cond.wait(timeout=self.heartbeat_timeout / 4)

    def _lost_locked(self, rank: int, why: str) -> None:  # requires-lock: _cond
        """Membership event: drop ``rank``, bump the generation, release
        every blocked collective/barrier with a rollback."""
        m = self._members.pop(rank, None)
        if m is None or m.gen != self._gen:
            return                       # already stale — counted once
        self._gen += 1
        self._events.append(f'gen={self._gen} lost rank {rank}: {why}')
        self.failure_log.record(
            'host_lost', f'rank {rank} left generation {self._gen - 1} '
            f'({why}); generation now {self._gen}')
        self._contrib.clear()
        self._barriers.clear()
        self._released.clear()
        self._result = None
        self._cond.notify_all()
        cb = self.on_host_lost
        if cb is not None:
            threading.Thread(target=cb, args=(rank,),
                             name='cxxnet-elastic-lost-cb',
                             daemon=True).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        rank = None
        is_hb = False
        try:
            while True:
                hdr, bufs = recv_frame(conn)
                op = hdr['op']
                if op == 'hb_attach':
                    rank = int(hdr['rank'])
                    is_hb = True
                    continue
                if op == 'hb':
                    with self._cond:
                        m = self._members.get(rank)
                        if m is None:
                            m = self._hello.get(rank)
                        if m is not None:
                            m.last_hb = time.monotonic()
                    continue
                rank = int(hdr.get('rank', -1))
                if op == 'hello':
                    self._op_hello(conn, rank)
                elif op == 'push':
                    self._op_push(conn, rank, hdr, bufs)
                elif op == 'barrier':
                    self._op_barrier(conn, rank, hdr)
                elif op == 'fault':
                    self._op_fault(conn, rank, hdr)
                elif op == 'bye':
                    with self._cond:
                        m = self._members.get(rank)
                        if m is not None and m.conn is conn:
                            # graceful leave after the done barrier: not
                            # a membership fault
                            self._members.pop(rank, None)
                    send_frame(conn, {'op': 'ok'})
                    return
                else:
                    send_frame(conn, {'op': 'error',
                                      'error': f'unknown op {op!r}'})
        except (ConnectionError, OSError, ValueError, KeyError) as e:
            with self._cond:
                if self._stop:
                    return
                if rank is not None and rank in self._hello \
                        and self._hello[rank].conn is conn:
                    # died while waiting in a rendezvous: un-register so
                    # a respawn's hello can take the slot
                    self._hello.pop(rank, None)
                if rank is not None and not is_hb \
                        and rank in self._members \
                        and self._members[rank].conn is conn:
                    self._lost_locked(rank, f'connection dropped ({e!r})')
                elif rank is not None and is_hb:
                    # a dying process drops its heartbeat socket first —
                    # use it as an early loss signal
                    if rank in self._members \
                            and self._members[rank].gen == self._gen:
                        self._lost_locked(
                            rank, f'heartbeat connection dropped ({e!r})')
        finally:
            try:
                conn.close()
            except OSError:
                pass
            # long-lived coordinators see endless reconnect churn: drop
            # this handler's bookkeeping so the lists stay bounded by
            # LIVE connections, not historical ones
            me = threading.current_thread()
            with self._cond:
                if conn in self._conns:
                    self._conns.remove(conn)
                if me in self._threads:
                    self._threads.remove(me)

    def _op_hello(self, conn: socket.socket, rank: int) -> None:
        """Rendezvous: one hello per rank; when all ``nhosts`` ranks are
        waiting, the generation is sealed and everyone gets a welcome."""
        with self._cond:
            if not 0 <= rank < self.nhosts:
                send_frame(conn, {'op': 'error',
                                  'error': f'rank {rank} out of range '
                                           f'0..{self.nhosts - 1}'})
                return
            # a re-hello replaces any stale registration for the rank —
            # and releases the superseded hello's parked handler (gen=-2
            # sentinel), or its thread would poll until stop()
            self._members.pop(rank, None)
            old = self._hello.get(rank)
            if old is not None:
                old.gen = -2
                self._cond.notify_all()
            me = _Member(rank, conn)
            self._hello[rank] = me
            if len(self._hello) == self.nhosts:
                # seal: the waiting hellos become the new generation's
                # membership (a bump mid-rendezvous just means they seal
                # into the newer generation)
                gen = self._gen
                for r, m in self._hello.items():
                    m.gen = gen
                    m.last_hb = time.monotonic()
                    self._members[r] = m
                self._hello.clear()
                self._welcomed_gen = gen
                self._events.append(
                    f'gen={gen} rendezvous complete ({self.nhosts} '
                    'hosts)')
                self._cond.notify_all()
            else:
                while not self._stop and me.gen == -1:
                    self._cond.wait(timeout=1.0)
            if me.gen == -2:
                # superseded by a newer hello from the same rank (the
                # client gave up and reconnected): this reply pairs
                # with a request nobody is waiting on — end the conn
                send_frame(conn, {'op': 'rollback', 'gen': self._gen,
                                  'why': 'superseded by a newer hello'})
                return
            gen = me.gen if me.gen >= 0 else self._gen
        send_frame(conn, {'op': 'welcome', 'gen': gen,
                          'nhosts': self.nhosts})

    def _op_push(self, conn: socket.socket, rank: int, hdr: dict,
                 bufs: List[bytes]) -> None:
        """Gradient-shard gather-broadcast: stash this host's shard
        payloads; when every member has pushed, hand the full assembled
        set back to each of them (the ps-lite Push+Pull pair in one
        round trip)."""
        with self._cond:
            m = self._members.get(rank)
            if m is None or m.gen != self._gen:
                send_frame(conn, {'op': 'rollback', 'gen': self._gen,
                                  'why': 'stale generation'})
                return
            if any(self._barriers.values()):
                # a peer is already waiting at a barrier while this host
                # still pushes steps: the hosts disagree about where the
                # run is — a config skew, not a transient
                send_frame(conn, {'op': 'error',
                                  'error': 'peers disagree: a host is at '
                                           'a barrier while this one '
                                           'still trains (step/config '
                                           'skew)'})
                return
            my_gen = self._gen
            step = int(hdr['step'])
            self._contrib[rank] = (hdr, bufs)
            if len(self._contrib) == self.nhosts:
                shards: Dict[int, Tuple[bytes, bytes]] = {}
                steps = set()
                for h, bs in self._contrib.values():
                    steps.add(int(h['step']))
                    for i, sid in enumerate(h['shards']):
                        shards[int(sid)] = (bs[2 * i], bs[2 * i + 1])
                if len(steps) != 1:
                    self._result = ('error',
                                    f'hosts pushed different steps '
                                    f'{sorted(steps)}')
                else:
                    order = sorted(shards)
                    flat = []
                    for sid in order:
                        flat += [shards[sid][0], shards[sid][1]]
                    self._result = ('pull', {'step': step,
                                             'shards': order}, flat)
                # version the result by step: a fast host may push step
                # t+1 before every peer consumed step t's result, and
                # must wait for ITS step, not adopt the stale one
                self._result_step = step
                self._result_left = self.nhosts
                self._contrib.clear()
                self._cond.notify_all()
            else:
                while (not self._stop and self._gen == my_gen
                       and not (self._result is not None
                                and self._result_step == step)):
                    self._cond.wait(timeout=1.0)
            if self._gen != my_gen or self._result is None \
                    or self._result_step != step:
                send_frame(conn, {'op': 'rollback', 'gen': self._gen,
                                  'why': 'membership changed mid-step'})
                return
            result = self._result
            self._result_left -= 1
            if self._result_left == 0:
                self._result = None
        if result[0] == 'error':
            send_frame(conn, {'op': 'error', 'error': result[1]})
        else:
            send_frame(conn, dict(result[1], op='pull'),
                       tuple(result[2]))

    def _op_barrier(self, conn: socket.socket, rank: int,
                    hdr: dict) -> None:
        """All-hosts fence, with a value exchange: release carries every
        member's value keyed by rank (the save gate, the restore-step
        broadcast, and the CRC verify all ride this one op)."""
        tag = str(hdr['tag'])
        with self._cond:
            m = self._members.get(rank)
            if m is None or m.gen != self._gen:
                send_frame(conn, {'op': 'rollback', 'gen': self._gen,
                                  'why': 'stale generation'})
                return
            my_gen = self._gen
            waiting = self._barriers.setdefault(tag, {})
            waiting[rank] = hdr.get('value')
            if len(waiting) == self.nhosts:
                self._released[tag] = (my_gen, self.nhosts, dict(waiting))
                del self._barriers[tag]
                self._cond.notify_all()
            else:
                while (not self._stop and self._gen == my_gen
                       and not (tag in self._released
                                and self._released[tag][0] == my_gen)):
                    self._cond.wait(timeout=1.0)
            rel = self._released.get(tag)
            if self._gen != my_gen or rel is None or rel[0] != my_gen:
                send_frame(conn, {'op': 'rollback', 'gen': self._gen,
                                  'why': 'membership changed at barrier'})
                return
            values = rel[2]
            left = rel[1] - 1
            if left == 0:
                del self._released[tag]
            else:
                self._released[tag] = (rel[0], left, values)
        send_frame(conn, {'op': 'release', 'tag': tag,
                          'values': {str(r): v for r, v in values.items()}})

    def _op_fault(self, conn: socket.socket, rank: int,
                  hdr: dict) -> None:
        """A worker reports a recoverable fault: bump the generation so
        every peer rolls back with it (deterministic faults — NaN at
        step S — arrive from all hosts; the bump happens once)."""
        with self._cond:
            m = self._members.get(rank)
            if m is not None and m.gen == self._gen:
                self._gen += 1
                self._events.append(
                    f'gen={self._gen} rank {rank} reported fault: '
                    f'{hdr.get("kind", "?")} at step {hdr.get("step")}')
                self._members.pop(rank, None)
                self._contrib.clear()
                self._barriers.clear()
                self._released.clear()
                self._result = None
                self._cond.notify_all()
            else:
                # stale or already-dropped member: the generation already
                # moved past this fault
                self._members.pop(rank, None)
        send_frame(conn, {'op': 'ok', 'gen': self.generation()})


# --- client ----------------------------------------------------------------


class ElasticClient:
    """One worker's connection to the coordinator: a synchronous op
    socket (the step loop's push/barrier round trips) plus a one-way
    heartbeat socket driven by a ``cxxnet-elastic-hb`` thread.

    Failure mapping: a reply of ``rollback`` → ``faults.HostLossError``
    (recoverable — restore and rendezvous); a dead/unresponsive socket →
    ``faults.CoordinatorUnreachableError`` (recoverable — from here a
    coordinator outage and a partition look the same); an ``error``
    reply → ``faults.ElasticSyncError`` (NOT recoverable: the hosts
    disagree about the run itself)."""

    def __init__(self, address: str, rank: int, nhosts: int,
                 heartbeat: float = 2.0, sync_timeout: float = 60.0,
                 rendezvous_timeout: float = 120.0):
        host, _, port = address.rpartition(':')
        self.host, self.port = host or '127.0.0.1', int(port)
        self.rank = int(rank)
        self.nhosts = int(nhosts)
        self.heartbeat = float(heartbeat)
        self.sync_timeout = float(sync_timeout)
        self.rendezvous_timeout = float(rendezvous_timeout)
        self.generation = -1          # guarded-by: _lock
        # per-generation barrier sequence numbers: barriers are lockstep
        # within a generation, so scoping the wire tag by (gen, seq)
        # keeps a fast host's NEXT use of a tag distinct from a slow
        # peer's not-yet-consumed release of the previous one
        self._bar_seq: Dict[str, int] = {}   # guarded-by: _lock
        self._lock = threading.Lock()
        self._sock: Optional[socket.socket] = None   # guarded-by: _lock
        self._hb_sock: Optional[socket.socket] = None
        self._silent_until = 0.0      # guarded-by: _lock
        self._closed = False          # guarded-by: _lock
        self._hb_thread: Optional[threading.Thread] = None

    # -- plumbing --
    def _dial(self) -> socket.socket:
        s = socket.create_connection((self.host, self.port), timeout=10.0)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return s

    def connect(self) -> None:
        sock, hb = self._dial(), self._dial()
        send_frame(hb, {'op': 'hb_attach', 'rank': self.rank})
        with self._lock:
            old = (self._sock, self._hb_sock)
            self._sock, self._hb_sock = sock, hb
        for s in old:
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass
        if self._hb_thread is None:
            self._hb_thread = threading.Thread(
                target=self._hb_loop, name=f'cxxnet-elastic-hb-{self.rank}',
                daemon=True)
            self._hb_thread.start()

    def _hb_loop(self) -> None:
        while True:
            with self._lock:
                if self._closed:
                    return
                sock = self._hb_sock
                silent = time.monotonic() < self._silent_until
            if sock is not None and not silent:
                try:
                    send_frame(sock, {'op': 'hb'})
                except OSError:
                    pass              # reconnects ride the next resync
            time.sleep(self.heartbeat)

    def _call(self, hdr: dict, bufs: Tuple[bytes, ...] = (),
              timeout: Optional[float] = None) -> Tuple[dict, List[bytes]]:
        """One synchronous round trip; maps transport failures onto the
        typed taxonomy (see class docstring)."""
        op = hdr['op']
        timeout = self.sync_timeout if timeout is None else timeout
        with self._lock:
            sock = self._sock
        if sock is None:
            raise faults.CoordinatorUnreachableError(op, 0.0)
        try:
            sock.settimeout(timeout)
            send_frame(sock, dict(hdr, rank=self.rank), bufs)
            reply, rbufs = recv_frame(sock)
        except (socket.timeout, TimeoutError, ConnectionError, OSError) \
                as e:
            # the socket is now DIRTY: a late reply to this op would
            # pair with the next request and desync every reply after
            # it.  Drop it — resync()/connect() dials fresh.
            with self._lock:
                if self._sock is sock:
                    self._sock = None
            try:
                sock.close()
            except OSError:
                pass
            raise faults.CoordinatorUnreachableError(op, timeout) from e
        if reply['op'] == 'rollback':
            with self._lock:
                self.generation = int(reply['gen'])
            raise faults.HostLossError(reply.get('why', 'rollback'),
                                       generation=int(reply['gen']))
        if reply['op'] == 'error':
            raise faults.ElasticSyncError(
                f'elastic {op} failed: {reply.get("error")}')
        return reply, rbufs

    # -- surface --
    def rendezvous(self) -> int:
        """Join the current membership generation (blocks until all
        ``nhosts`` ranks are present).  Returns the sealed generation."""
        from ..obs import span
        with span('elastic.rendezvous', 'elastic',
                  rank=self.rank) as sp:
            reply, _ = self._call({'op': 'hello'},
                                  timeout=self.rendezvous_timeout)
            if reply['op'] != 'welcome':
                raise faults.ElasticSyncError(
                    f'expected welcome, got {reply["op"]!r}')
            with self._lock:
                self.generation = int(reply['gen'])
                self._bar_seq.clear()
                sp.attrs['gen'] = self.generation
                return self.generation

    def all_shards(self, step: int, shard_ids: List[int],
                   flats: List[np.ndarray], losses: List[np.ndarray],
                   ) -> Tuple[Dict[int, np.ndarray], Dict[int, np.float32]]:
        """Push this host's shard gradients, pull the full set (every
        shard's bytes exactly as some host pushed them)."""
        from ..obs import span
        bufs: List[bytes] = []
        for f, l in zip(flats, losses):
            bufs.append(np.ascontiguousarray(f, np.float32).tobytes())
            bufs.append(np.ascontiguousarray(l, np.float32).tobytes())
        with span('elastic.push_pull', 'elastic', step=int(step),
                  rank=self.rank, shards=len(shard_ids)):
            reply, rbufs = self._call(
                {'op': 'push', 'step': int(step),
                 'shards': [int(s) for s in shard_ids]}, tuple(bufs))
        out_f: Dict[int, np.ndarray] = {}
        out_l: Dict[int, np.float32] = {}
        for i, sid in enumerate(reply['shards']):
            out_f[int(sid)] = np.frombuffer(rbufs[2 * i], np.float32)
            out_l[int(sid)] = np.frombuffer(rbufs[2 * i + 1],
                                            np.float32)[0]
        return out_f, out_l

    def barrier(self, tag: str, value=None,
                timeout: Optional[float] = None) -> Dict[int, object]:
        """Fence with all hosts; returns every member's value by rank.
        Wire tags are scoped by (generation, per-tag sequence) — all
        hosts execute the same barrier sequence within a generation, so
        the scoped tags line up by construction."""
        from ..obs import span
        with self._lock:
            seq = self._bar_seq.get(tag, 0)
            self._bar_seq[tag] = seq + 1
            wire = f'{self.generation}/{tag}#{seq}'
        with span('elastic.barrier', 'elastic', tag=tag,
                  rank=self.rank, wire=wire):
            reply, _ = self._call({'op': 'barrier', 'tag': wire,
                                   'value': value}, timeout=timeout)
        return {int(r): v for r, v in reply['values'].items()}

    def report_fault(self, kind: str, step: int) -> None:
        """Tell the coordinator this host is rolling back (peers must
        too).  Best-effort: if the coordinator already noticed — or is
        unreachable — the rendezvous will sort it out."""
        try:
            self._call({'op': 'fault', 'kind': kind, 'step': int(step)},
                       timeout=min(10.0, self.sync_timeout))
        except (faults.HostLossError, faults.CoordinatorUnreachableError,
                faults.ElasticSyncError):
            pass

    def resync(self, kind: str, step: int) -> int:
        """Recovery path: report the fault, reconnect if the transport
        died, and rendezvous into the next generation."""
        self.report_fault(kind, step)
        try:
            return self.rendezvous()
        except faults.CoordinatorUnreachableError:
            self.connect()            # partition healed / socket died
            return self.rendezvous()

    def partition(self, secs: float) -> None:
        """Deterministic network partition: stop heartbeating and go
        silent for ``secs`` (the ``partition=N:secs`` fault event)."""
        with self._lock:
            self._silent_until = time.monotonic() + secs
        time.sleep(secs)

    def abort(self) -> None:
        """Drop both sockets with NO goodbye — the abrupt-death
        simulation (the coordinator sees exactly what a preempted
        process leaves behind: dead connections)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            socks = (self._sock, self._hb_sock)
            self._sock = self._hb_sock = None
        for s in socks:
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass
        t = self._hb_thread
        if t is not None:
            t.join(timeout=self.heartbeat + 2.0)

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            sock, hb = self._sock, self._hb_sock
            self._sock = self._hb_sock = None
        for s in (sock, hb):
            if s is None:
                continue
            try:
                if s is sock:
                    s.settimeout(2.0)
                    send_frame(s, {'op': 'bye', 'rank': self.rank})
                    recv_frame(s)
            except OSError:          # ConnectionError included
                pass
            try:
                s.close()
            except OSError:
                pass
        t = self._hb_thread
        if t is not None:
            t.join(timeout=self.heartbeat + 2.0)


# --- the elastic step ------------------------------------------------------


@dataclass
class ElasticConfig:
    """Shape of one elastic job (config keys in doc/tasks.md)."""

    hosts: int = 1                 # dist.hosts
    rank: int = 0                  # dist.rank
    shards: int = 0                # dist.shards (0 = hosts)
    coordinator: str = ''          # dist.coordinator host:port
    heartbeat: float = 2.0         # dist.heartbeat seconds
    rejoin: int = 2                # dist.rejoin respawn budget (launcher)
    sync_timeout: float = 60.0     # dist.sync_timeout seconds
    incarnation: int = 0           # CXXNET_ELASTIC_INCARNATION
    batch_size: int = 0            # GLOBAL batch size (conf batch_size)

    def resolve(self) -> 'ElasticConfig':
        self.shards = self.shards or self.hosts
        if self.hosts < 1:
            raise ValueError(f'dist.hosts must be >= 1, got {self.hosts}')
        if not 0 <= self.rank < self.hosts:
            raise faults.DistInitError(
                f'dist.rank {self.rank} out of range for dist.hosts='
                f'{self.hosts}')
        if self.shards % self.hosts:
            raise ValueError(
                f'dist.shards={self.shards} must be a multiple of '
                f'dist.hosts={self.hosts} (each shard lives on exactly '
                'one host)')
        if self.batch_size % self.shards:
            raise ValueError(
                f'batch_size={self.batch_size} must divide into '
                f'dist.shards={self.shards} equal micro-shards')
        return self

    @property
    def owned_shards(self) -> List[int]:
        return [s for s in range(self.shards) if s % self.hosts == self.rank]


class ElasticStepper:
    """The elastic step loop body (the supervisor's ``make_stepper``
    protocol: ``feed``/``finish``/``discard``).

    One host batch (``batch_size/hosts`` rows, the host's stride of the
    global batch) = one optimizer step: per owned micro-shard, a
    grad-only dispatch (``trainer.compile_grad_step``); one push/pull
    with the coordinator; a fixed-ascending-order fold of ALL shard
    bytes (including this host's own, as transported — every host folds
    identical f32 buffers); one jitted optimizer apply.  The fold's
    shape depends only on ``dist.shards`` — never on the host count —
    which is the whole bitwise-at-any-host-count invariant."""

    def __init__(self, trainer, client: ElasticClient, cfg: ElasticConfig,
                 grad_fn=None, apply_fn=None):
        import jax
        self.tr = trainer
        self.client = client
        self.cfg = cfg
        self.grad_fn = grad_fn if grad_fn is not None \
            else trainer.compile_grad_step()
        self.apply_fn = apply_fn if apply_fn is not None \
            else trainer.compile_apply_grad()
        self.updates = 0
        # gradient wire format, fixed at construction from params (the
        # gradients' structure and shardings; an update_period = 1
        # trainer keeps no accumulator to read them from)
        leaves, self._treedef = jax.tree.flatten(trainer.params)
        self._leaf_shapes = [l.shape for l in leaves]
        self._leaf_sizes = [int(np.prod(s)) for s in self._leaf_shapes]
        self._leaf_shardings = [l.sharding for l in leaves]
        for l in leaves:
            if l.dtype != np.float32:
                raise ValueError(
                    'elastic training requires float32 params/grads '
                    f'(got {l.dtype}) — the wire fold is defined over '
                    'f32 bytes')

    def _flatten(self, grads) -> np.ndarray:
        import jax
        return np.concatenate(
            [np.asarray(l, np.float32).ravel()
             for l in jax.tree.leaves(grads)])

    def _unflatten_to_device(self, flat: np.ndarray):
        import jax
        leaves = []
        off = 0
        for shape, size, sh in zip(self._leaf_shapes, self._leaf_sizes,
                                   self._leaf_shardings):
            leaves.append(jax.device_put(
                flat[off:off + size].reshape(shape), sh))
            off += size
        return jax.tree.unflatten(self._treedef, leaves)

    def feed(self, batch) -> int:
        import jax
        tr = self.tr
        cfg = self.cfg
        step = tr.sample_counter
        # chaos hooks: host_loss kills this process here; partition goes
        # silent for N seconds before the step's collective
        secs = faults.elastic_step(step, cfg.rank, cfg.hosts,
                                   allow_kill=cfg.incarnation == 0)
        if secs:
            self.client.partition(secs)
        if batch.extra_data:
            raise ValueError('elastic training does not support '
                             'extra_data (attachtxt) chains')
        q = cfg.shards // cfg.hosts
        data = np.asarray(batch.data)
        label = np.asarray(batch.label)
        bs = batch.batch_size
        mask = np.ones(bs, np.float32)
        if batch.num_batch_padd and getattr(batch, 'pad_synthetic', False):
            mask[bs - batch.num_batch_padd:] = 0.0
        norm = tr._norm_args(batch)
        step_rng = jax.random.fold_in(
            tr._rng, 1 + step * 131 + tr.round)
        owned = cfg.owned_shards
        flats: List[np.ndarray] = []
        losses: List[np.ndarray] = []
        for s in owned:
            j0 = (s - cfg.rank) // cfg.hosts
            rows = slice(j0, None, q)
            d = tr._shard_batch(np.ascontiguousarray(data[rows]),
                                cast=not norm)
            l = tr._shard_batch(np.ascontiguousarray(label[rows]),
                                cast=False)
            m = tr._shard_batch(np.ascontiguousarray(mask[rows]),
                                cast=False)
            loss, grads = self.grad_fn(
                tr.params, d, l, (), m, jax.random.fold_in(step_rng, s),
                tr.round, norm=norm)
            flats.append(self._flatten(grads))
            losses.append(np.asarray(loss, np.float32).reshape(1))
        full, full_loss = self.client.all_shards(step, owned, flats,
                                                 losses)
        if sorted(full) != list(range(cfg.shards)):
            raise faults.ElasticSyncError(
                f'step {step}: pulled shards {sorted(full)}, expected '
                f'0..{cfg.shards - 1}')
        # the fixed-order fold: ascending shard id, then one 1/S scale —
        # identical bytes in, identical bytes out, on every host
        inv = np.float32(1.0 / cfg.shards)
        acc = full[0].copy()
        loss_acc = np.float32(full_loss[0])
        for s in range(1, cfg.shards):
            acc += full[s]
            loss_acc = np.float32(loss_acc + full_loss[s])
        acc *= inv
        loss_acc = np.float32(loss_acc * inv)
        gtree = self._unflatten_to_device(acc)
        tr.params, tr.opt_state = self.apply_fn(
            tr.params, tr.opt_state, gtree, tr.epoch_counter)
        tr._observe_loss(loss_acc)
        tr.epoch_counter += 1
        tr.sample_counter += 1
        self.updates += 1
        return 1

    def finish(self) -> int:
        return 0

    def discard(self) -> None:
        pass


# --- supervisor ------------------------------------------------------------


class ElasticSupervisor(TrainSupervisor):
    """``TrainSupervisor`` with the cross-host choreography layered on:

    * every gate-accepted save is an all-hosts barrier; rank 0 writes
      (shared checkpoint storage; params are bitwise-replicated, so one
      writer IS the fleet's checkpoint) — with ``save_async`` the
      barrier fences the snapshot and the ``AsyncCheckpointer`` commits
      behind the step loop exactly as on one host,
    * recovery rendezvouses the next membership generation (waiting out
      a respawned replacement), restores rank 0 first (quarantine
      authority is singular), broadcasts the restored step, then proves
      the resume with a params-CRC barrier,
    * ``HostLossError``/``CoordinatorUnreachableError`` join the
      RECOVERABLE set: a lost peer is a restore-and-rejoin, never a
      dead run.
    """

    RECOVERABLE = TrainSupervisor.RECOVERABLE + (
        faults.HostLossError, faults.CoordinatorUnreachableError)

    def __init__(self, trainer, ckpt_dir: str, config: SupervisorConfig,
                 client: ElasticClient, elastic: ElasticConfig,
                 failure_log: Optional[faults.FailureLog] = None):
        super().__init__(trainer, ckpt_dir, config, failure_log)
        self.client = client
        self.elastic = elastic

    def _have_step(self, step: int) -> bool:
        from ..nnet import sharded_ckpt
        return os.path.isdir(sharded_ckpt.step_dir(self.ckpt_dir, step))

    def save(self) -> str:
        """Cross-host gate-accepted save: fence all hosts at the step,
        rank 0 writes.  A step already on disk is skipped WITHOUT a
        barrier — that is the rejoining replacement's entry anchor,
        whose peers (mid-recovery survivors) are not at an anchor point
        and must not be waited on."""
        from ..nnet import sharded_ckpt
        step = self.trainer.sample_counter
        if self._have_step(step):
            self.failure_log.record(
                'save_skipped', f'step {step} already checkpointed '
                '(rejoin anchor)', step=step)
            return sharded_ckpt.step_dir(self.ckpt_dir, step)
        vals = self.client.barrier('save', value=step)
        if len(set(vals.values())) != 1:
            raise faults.ElasticSyncError(
                f'hosts arrived at the save barrier with different '
                f'steps: {vals}')
        if self.elastic.rank == 0:
            return super().save()
        self.failure_log.record(
            'save_delegated', f'step {step} saved by rank 0', step=step)
        if self.config.on_save is not None:
            self.config.on_save(step)
        return sharded_ckpt.step_dir(self.ckpt_dir, step)

    def restore(self) -> int:
        """Recovery: resync membership (new generation), then the
        coordinated restore."""
        self.client.resync('restore', self.trainer.sample_counter)
        return self.restore_synced()

    def restore_synced(self) -> int:
        """The coordinated restore itself — also the entry path for a
        rejoining worker that already rendezvoused: rank 0 restores
        resiliently (it alone may quarantine corrupt steps), broadcasts
        the landed step, peers restore that exact step, and a CRC
        barrier proves every host resumed from identical params."""
        from ..obs import span
        tr = self.trainer
        with span('elastic.restore', 'elastic', rank=self.elastic.rank):
            return self._restore_synced_inner(tr)

    def _restore_synced_inner(self, tr) -> int:
        if self.elastic.rank == 0:
            step = super().restore()
            self.client.barrier('restore', value=step)
        else:
            vals = self.client.barrier('restore', value=None)
            step = vals.get(0)
            if step is None:
                raise faults.ElasticSyncError(
                    'restore barrier released without rank 0\'s step')
            tr.reset_transient_state()
            tr.load_training_state(self.ckpt_dir, step=int(step),
                                   restore_params=True,
                                   retry=self.config.retry)
            self.failure_log.record('restored',
                                    f'resumed from step {step} (rank 0 '
                                    'authority)', step=int(step))
        crc = params_crc(tr.params)
        vals = self.client.barrier('verify', value=f'{step}:{crc}')
        if len(set(vals.values())) != 1:
            raise faults.ElasticSyncError(
                f'post-restore state diverged across hosts: {vals}')
        return int(step)


# --- worker driver ---------------------------------------------------------


def _find_augment(it):
    from ..io.iter_augment import AugmentIterator
    node = it
    while node is not None:
        if isinstance(node, AugmentIterator):
            return node
        node = getattr(node, 'base', None)
    return None


def elastic_train(task) -> None:
    """One elastic worker's whole training run, driven from the CLI
    (``task`` is ``main.LearnTask`` after ``init()``).  Single
    supervised ``run()`` over ``num_round`` epoch passes of the
    host-sharded stream; recovery — local faults, peer loss, this
    host's own rejoin after a respawn — all lands inside it.

    The in-process convenience path (``dist.hosts=1`` with no
    coordinator) spins a local :class:`ElasticCoordinator` thread, so a
    single-host elastic run needs no launcher — that run IS the
    bitwise twin the multi-host drills compare against."""
    import sys

    from ..io.data import ThreadBufferIterator
    from ..nnet import sharded_ckpt

    tr = task.net_trainer
    ecfg = ElasticConfig(
        hosts=task.dist_hosts, rank=max(0, task.dist_rank),
        shards=task.dist_shards, coordinator=task.dist_coordinator,
        heartbeat=task.dist_heartbeat, rejoin=task.dist_rejoin,
        sync_timeout=task.dist_sync_timeout,
        incarnation=int(os.environ.get('CXXNET_ELASTIC_INCARNATION',
                                       '0') or 0),
        batch_size=tr.batch_size).resolve()
    if tr.update_period != 1:
        raise ValueError(
            'elastic training owns the accumulate/apply split '
            '(dist.shards micro-shards per step); update_period must '
            'stay 1')
    top = task.itr_train
    if top is None:
        raise ValueError('elastic training needs a data= section')
    it = top.base if isinstance(top, ThreadBufferIterator) else top
    if not it.is_replay_stable():
        raise ValueError(
            'elastic recovery re-winds the stream bitwise: the train '
            'iterator must be replay-stable (imgbin/imgbin_stream with '
            'shuffle=0)')
    aug = _find_augment(it)
    if aug is None:
        raise ValueError(
            'elastic host sharding rides the augment stage\'s pooled '
            'thunk stream — use an imgbin-family iterator '
            '(iter=imgbin/imgbinx/imgbin_stream)')
    if aug.nworker == 0:
        top.set_param('nworker', '1')
    top.set_param('elastic_hosts', str(ecfg.hosts))
    top.set_param('elastic_rank', str(ecfg.rank))
    top.set_param('batch_size', str(ecfg.batch_size // ecfg.hosts))

    coord = None
    addr = ecfg.coordinator
    if not addr or addr == 'local':
        if ecfg.hosts != 1:
            raise ValueError(
                'dist.coordinator=host:port is required when '
                'dist.hosts > 1 (the launcher passes it to every '
                'worker)')
        coord = ElasticCoordinator(1,
                                   heartbeat_timeout=ecfg.heartbeat * 5)
        addr = coord.start()
    client = ElasticClient(addr, ecfg.rank, ecfg.hosts,
                           heartbeat=ecfg.heartbeat,
                           sync_timeout=ecfg.sync_timeout)
    ckpt_dir = os.path.join(task.name_model_dir, 'elastic_state')
    sup_cfg = SupervisorConfig(
        batch_deadline=task.watchdog_deadline or None,
        max_restarts=task.max_restarts,
        nan_breaker=task.nan_breaker,
        save_every=task.save_every,
        keep_last=task.keep_last,
        # one writer: peers fence at the save barrier but never touch
        # the shared checkpoint storage
        save_async=task.save_async if ecfg.rank == 0 else 0,
        save_workers=task.save_workers,
        pipeline_stats=it.pipeline_stats())
    sup = ElasticSupervisor(tr, ckpt_dir, sup_cfg, client, ecfg)
    # every worker registers into the process-wide telemetry hub: the
    # elastic gauges ride /metrics and the generation + membership view
    # rides /statusz (each worker process has its own hub + endpoints)
    from ..obs import get_hub
    from ..utils.metric import StatSet
    estats = StatSet()

    def _refresh_elastic():
        estats.gauge('rank', ecfg.rank)
        estats.gauge('hosts', ecfg.hosts)
        estats.gauge('generation', client.generation)
        estats.gauge('incarnation', ecfg.incarnation)
        estats.gauge('steps', tr.sample_counter)
        estats.gauge('restarts', sup.restarts_total)

    get_hub().register_stats('elastic', estats, refresh=_refresh_elastic)
    get_hub().register_status(
        'elastic', lambda: {'rank': ecfg.rank, 'hosts': ecfg.hosts,
                            'generation': client.generation,
                            'incarnation': ecfg.incarnation,
                            'shards': list(ecfg.owned_shards),
                            'steps': int(tr.sample_counter),
                            'restarts': sup.restarts_total})
    try:
        client.connect()
        gen = client.rendezvous()
        if not task.silent:
            print(f'elastic worker rank {ecfg.rank}/{ecfg.hosts}: joined '
                  f'generation {gen} (shards {ecfg.owned_shards}, '
                  f'incarnation {ecfg.incarnation})', flush=True)
        if gen > 0 or sharded_ckpt.all_steps(ckpt_dir):
            # rejoin (or a cold full-fleet resume): adopt the committed
            # step every peer restores, before the first batch
            sup.restore_synced()
        entry = tr.sample_counter
        num_round = task.num_round
        tr.round = 0           # one supervised run; RNG keys on step only

        def factory(k):
            def passes():
                for _ in range(num_round):
                    for b in iter(it):
                        yield b
            return itertools.islice(passes(), k + entry, None)

        n = sup.run(factory,
                    make_stepper=lambda: ElasticStepper(tr, client, ecfg))
        final = tr.sample_counter
        crc = params_crc(tr.params)
        vals = client.barrier('done', value=f'{final}:{crc}')
        if len(set(vals.values())) != 1:
            raise faults.ElasticSyncError(
                f'final state diverged across hosts: {vals}')
        if ecfg.rank == 0:
            if task.itr_evals:
                sys.stderr.write('[dist]')
                for ev, name in zip(task.itr_evals, task.eval_names):
                    sys.stderr.write(tr.evaluate(ev, name))
                sys.stderr.write('\n')
                sys.stderr.flush()
            task.start_counter = max(task.start_counter, task.num_round)
            task._save_model()
        # the headline receipt every drill greps: step + params crc —
        # twins across host counts / fault plans must print the same crc
        print(f'[elastic] rank {ecfg.rank} done: steps={final} '
              f'updates={n} params_crc={crc} '
              f'generation={client.generation} '
              f'restarts={sup.restarts_total}', flush=True)
    finally:
        sup.close()
        client.close()
        if coord is not None:
            coord.stop()


# --- launcher --------------------------------------------------------------


class ElasticLauncher:
    """Spawn, monitor, and respawn the per-host worker processes (the
    single-machine stand-in for the fleet's cluster manager, like
    ``tools/launch_dist.py`` for the jax.distributed path).  Owns the
    coordinator, so losing any worker — rank 0 included — never kills
    the membership service.  A worker that dies (preemption drill,
    crash, kill -9) is respawned with an incremented
    ``CXXNET_ELASTIC_INCARNATION`` while the ``dist.rejoin`` budget
    lasts; it rejoins the rendezvous and the run continues.

    Fleet observability (doc/observability.md "Fleet view"): with
    ``fleet_port >= 0``, fleet-scoped ``slo_specs``, or a
    ``trace_merge`` path, every worker gets an ephemeral ObsServer
    (``obs.port=0``) announcing its port into a per-rank file; the
    launcher scrapes each rank's ``/metrics`` into ONE rank-labeled
    exposition (``obs.fleet_port=``), evaluates ``fleet.*`` SLOs across
    ranks from its own supervision loop, and at run end merges the
    per-rank Chrome traces into one Perfetto file with a lane per host.
    The scrape survives any rank's mid-run death — a dead rank's rows
    drop and ``cxxnet_fleet_ranks_alive`` dips until the respawn."""

    def __init__(self, argv: List[str], hosts: int, rejoin: int = 2,
                 heartbeat: float = 2.0, worker_cmd: Optional[List[str]]
                 = None, env: Optional[Dict[str, str]] = None,
                 cwd: Optional[str] = None, silent: bool = False,
                 poll: float = 0.2, fleet_port: int = -1,
                 sample_every: float = 0.5,
                 slo_specs: Optional[List[Tuple[str, str]]] = None,
                 trace_merge: str = ''):
        self.argv = list(argv)
        self.hosts = int(hosts)
        self.rejoin = int(rejoin)
        self.heartbeat = float(heartbeat)
        self.worker_cmd = worker_cmd
        self.env = env
        self.cwd = cwd
        self.silent = silent
        self.poll = float(poll)
        self.coordinator: Optional[ElasticCoordinator] = None
        self.respawns: List[Tuple[int, int]] = []   # (rank, incarnation)
        # fleet observability (None until the first worker announces)
        self.fleet_port = int(fleet_port)
        # <= 0 = "auto" (mirrors main._obs_start): the fleet default
        # cadence, never a negative clamped into a 100 Hz scrape loop
        self.sample_every = (float(sample_every)
                             if float(sample_every) > 0 else 0.5)
        self.slo_specs = list(slo_specs or [])
        self.trace_merge = str(trace_merge or '')
        self.fleet_server = None
        self.fleet_scraper = None
        self.fleet_slo = None
        self.fleet_verdicts: Dict[str, dict] = {}
        self.fleet_metrics = ''
        self._sampler = None
        self._obs_dir: Optional[str] = None
        self._ports: Dict[int, int] = {}     # rank -> announced port

    def _fleet_enabled(self) -> bool:
        return (self.fleet_port >= 0 or bool(self.trace_merge)
                or bool(self.slo_specs))

    def _port_file(self, rank: int) -> str:
        return os.path.join(self._obs_dir, f'rank{rank}.port')

    def _trace_file(self, rank: int) -> str:
        return os.path.join(self._obs_dir, f'trace_rank{rank}.json')

    def _spawn(self, rank: int, incarnation: int, addr: str):
        import subprocess
        import sys
        env = dict(os.environ if self.env is None else self.env)
        env['CXXNET_ELASTIC_INCARNATION'] = str(incarnation)
        # dev/CI harness semantics (like tools/launch_dist.py): every
        # worker is one "host" on this machine, pinned to CPU; a real
        # fleet runs one worker per host under its own scheduler
        env.setdefault('JAX_PLATFORMS', 'cpu')
        cmd = list(self.worker_cmd
                   or [sys.executable, '-m', 'cxxnet_tpu.main'])
        cmd += self.argv
        if self._obs_dir is not None:
            # ephemeral per-rank endpoint + port announce file; the
            # respawned incarnation re-announces into the same path, so
            # the scraper follows it to the new port
            env['CXXNET_OBS_PORT_FILE'] = self._port_file(rank)
            cmd += ['obs.port=0']
            if self.trace_merge:
                cmd += [f'obs.trace_export={self._trace_file(rank)}']
        cmd += [f'dist.hosts={self.hosts}', f'dist.rank={rank}',
                f'dist.coordinator={addr}']
        return subprocess.Popen(cmd, env=env, cwd=self.cwd)

    def _fleet_poll(self) -> None:
        """One supervision-loop beat of the fleet leg: adopt newly
        announced rank ports, stand the merged endpoint + SLO engine up
        once the first rank answers, and pace the fleet sampler."""
        if self._obs_dir is None:
            return
        from ..obs.fleet import FleetScraper, FleetServer
        for rank in range(self.hosts):
            try:
                with open(self._port_file(rank), encoding='utf-8') as f:
                    port = int(f.read().strip() or 0)
            except (OSError, ValueError):
                continue
            if port and self._ports.get(rank) != port:
                self._ports[rank] = port
                if self.fleet_scraper is None:
                    self.fleet_scraper = FleetScraper()
                self.fleet_scraper.add_target(
                    rank, f'http://127.0.0.1:{port}')
        if self.fleet_scraper is None:
            return
        if self._sampler is None:
            from ..obs.history import GaugeSampler
            self._sampler = GaugeSampler(self.fleet_scraper.source,
                                         period=self.sample_every)
            if self.slo_specs:
                from ..obs.slo import SLOEngine, SLOSpec
                self.fleet_slo = SLOEngine(self._sampler.history)
                for name, text in self.slo_specs:
                    self.fleet_slo.add(SLOSpec.parse(name, text))
                self._sampler.add_listener(self.fleet_slo.on_tick)
        if self.fleet_server is None and self.fleet_port >= 0:
            self.fleet_server = FleetServer(self.fleet_scraper,
                                            engine=self.fleet_slo,
                                            port=self.fleet_port)
            if not self.silent:
                print(f'obs: fleet telemetry on {self.fleet_server.url} '
                      '(/metrics /statusz /healthz /slos, rank labels)',
                      flush=True)
        # ONE scrape per beat serves both consumers: the sampler's
        # source() pass feeds the SLO history AND refreshes the
        # scraper's per-rank snapshots behind last_merged() — a second
        # scrape here would double every rank's GET (and double the
        # stall window a hung rank can inflict on this loop)
        self._sampler.maybe_tick()

    def _fleet_close(self) -> None:
        if self.fleet_scraper is not None:
            self.fleet_metrics = self.fleet_scraper.last_merged()
        if self.fleet_slo is not None:
            self.fleet_verdicts = self.fleet_slo.status_view()
            if not self.silent:
                from ..obs.slo import summary_lines
                for line in summary_lines(self.fleet_verdicts):
                    print(f'[fleet] {line}', flush=True)
        if self.fleet_server is not None:
            self.fleet_server.close(timeout=5.0)
        if self._sampler is not None:
            self._sampler.close(timeout=5.0)
        if self.trace_merge and self._obs_dir is not None:
            from ..obs.fleet import merge_chrome_traces
            out = merge_chrome_traces(
                {r: self._trace_file(r) for r in range(self.hosts)},
                self.trace_merge)
            if out and not self.silent:
                print(f'obs: merged fleet Chrome trace -> {out} '
                      '(one lane per host; load in Perfetto)', flush=True)
        if self._obs_dir is not None:
            import shutil
            shutil.rmtree(self._obs_dir, ignore_errors=True)
            self._obs_dir = None

    def run(self) -> int:
        coord = ElasticCoordinator(self.hosts,
                                   heartbeat_timeout=self.heartbeat * 5)
        self.coordinator = coord
        addr = coord.start()
        if self._fleet_enabled():
            import tempfile
            self._obs_dir = tempfile.mkdtemp(prefix='cxxnet-fleet-')
        incarn = {r: 0 for r in range(self.hosts)}
        procs = {r: self._spawn(r, 0, addr) for r in range(self.hosts)}
        done: Dict[int, int] = {}
        budget = self.rejoin
        rc_final = 0
        try:
            while len(done) < self.hosts:
                time.sleep(self.poll)
                for rank, p in list(procs.items()):
                    if rank in done or p.poll() is None:
                        continue
                    rc = p.returncode
                    if rc == 0:
                        done[rank] = 0
                        continue
                    if budget > 0:
                        budget -= 1
                        incarn[rank] += 1
                        self.respawns.append((rank, incarn[rank]))
                        if not self.silent:
                            print(f'elastic launcher: rank {rank} exited '
                                  f'rc={rc} — respawning (incarnation '
                                  f'{incarn[rank]}, {budget} rejoin(s) '
                                  'left)', flush=True)
                        procs[rank] = self._spawn(rank, incarn[rank],
                                                  addr)
                    else:
                        rc_final = rc
                        # lint: allow(fault-taxonomy): launcher-internal control flow, caught below
                        raise _LaunchAborted(rank, rc)
                if not done:
                    # sample only while NO rank has finished cleanly: a
                    # crashed/killed rank never enters `done` (it gets
                    # respawned), so every MID-run death still dips
                    # ranks_alive and the SLOs see it — but once the
                    # first rank completes, the fleet is winding down
                    # and a staggered-exit beat would overwrite the
                    # last full view with a partial one (and book a
                    # bogus teardown breach)
                    self._fleet_poll()
        except _LaunchAborted as e:
            if not self.silent:
                print(f'elastic launcher: rank {e.rank} failed rc='
                      f'{e.rc} with no rejoin budget left — aborting',
                      flush=True)
            for p in procs.values():
                if p.poll() is None:
                    p.kill()
            for p in procs.values():
                p.wait()
        finally:
            # NO parting scrape: the workers are (mostly) gone by now,
            # and sampling the empty fleet would overwrite the last
            # live snapshot with an all-dead window and book a bogus
            # teardown breach — fleet_metrics/fleet_verdicts keep the
            # newest state observed while ranks were answering
            self._fleet_close()
            coord.stop()
        return rc_final


class _LaunchAborted(Exception):
    def __init__(self, rank: int, rc: int):
        self.rank, self.rc = rank, rc
        super().__init__(f'rank {rank} rc={rc}')
