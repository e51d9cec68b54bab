"""Threaded event-loop Node API — the reference's goroutine/channel layer
(reference: node.go:132-243 Node interface, 271-289 StartNode, 343-454 run).

One Python thread per `NodeHost` owns the (thread-unsafe) `RawNodeBatch`
exactly like the reference's `node.run()` goroutine owns the RawNode; every
interaction crosses a queue, mirroring the reference's channel set
(propc/recvc/tickc/readyc/advancec/confc, node.go:297-310). All lanes of the
batch share one loop thread — the batched analog of "multinode which can host
multiple raft groups" (reference: raft.go:244-246).

The app-facing contract is the reference's (doc.go:69-145): take a Ready,
persist + send + apply, then Advance. `Node.ready()` blocks like `<-n.Ready()`.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Callable

from raft_tpu.api.rawnode import Message, RawNodeBatch, Ready
from raft_tpu.types import (
    LOCAL_APPEND_THREAD,
    LOCAL_APPLY_THREAD,
    LOCAL_MSGS,
    MessageType as MT,
)
from raft_tpu.utils.profiling import StepStats


class ErrStopped(Exception):
    """The node host was stopped while an operation waited (reference:
    node.go:36 ErrStopped, returned by every channel op racing `n.done`)."""


class ErrCanceled(Exception):
    """The caller's cancellation (or deadline) fired while the op waited —
    the context-cancellation arm of the reference's blocking calls
    (node.go:502-545 stepWaitOption select on ctx.Done()). Cancellation
    observed BEFORE the loop picks the op up guarantees it is skipped;
    cancellation racing the loop's execution may still see the op applied —
    exactly the reference's semantics, where a proposal already handed to
    the raft goroutine proceeds even as the caller returns ctx.Err()."""


# op lifecycle: PENDING -> STARTED (loop wins) xor CANCELED (waiter wins).
# The transition is taken under `lock`, making the reference's atomic select
# between the channel send and ctx.Done() (node.go:502-545) a real guarantee:
# a caller that observes CANCELED knows the loop will never execute the op.
_PENDING, _STARTED, _CANCELED = 0, 1, 2


@dataclasses.dataclass
class _Op:
    kind: str
    lane: int
    payload: object = None
    done: threading.Event | None = None
    result: object = None
    error: Exception | None = None
    # cancellation (the ctx.Done() analog): raced against execution via the
    # locked `state` transition; a canceled op is skipped, never half-applied
    cancel: threading.Event | None = None
    state: int = _PENDING
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)

    def try_start(self) -> bool:
        """Loop side: claim the op for execution. False if already canceled."""
        with self.lock:
            if self.state == _CANCELED:
                return False
            self.state = _STARTED
            return True

    def try_cancel(self) -> bool:
        """Waiter side: claim cancellation. False if the loop already won."""
        with self.lock:
            if self.state == _STARTED:
                return False
            self.state = _CANCELED
            return True


class NodeHost:
    """Owns the batch + loop thread; hands out per-lane `Node` views."""

    def __init__(self, batch: RawNodeBatch):
        self.batch = batch
        # per-op-kind wall timings on the loop thread (the reference's
        # callStats analog): node.<kind> spans, exported as
        # step_<kind>_count / step_<kind>_micros via stats.snapshot(),
        # registerable with metrics.host.MetricsRegistry
        self.stats = StepStats("node")
        self._ops: queue.Queue[_Op] = queue.Queue()
        self._ready_q: list[queue.Queue[Ready]] = [
            queue.Queue(maxsize=1) for _ in range(batch.shape.n)
        ]
        self._advance_pending = [False] * batch.shape.n
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def node(self, lane: int) -> "Node":
        return Node(self, lane)

    def stop(self):
        self._stop.set()
        self._ops.put(_Op("noop", 0))
        self._thread.join(timeout=10)

    # -- loop (reference: node.go:343-454) ---------------------------------

    def _run(self):
        while not self._stop.is_set():
            try:
                op = self._ops.get(timeout=0.01)
            except queue.Empty:
                op = None
            if op is not None:
                self._handle(op)
            # surface Readys for lanes that want them (readyc select arm);
            # ready_lanes is the batched egress mask — ONE device dispatch
            # for all lanes instead of a scalar has_ready poll per lane
            with self.stats.span("node.ready_poll"):
                lanes = self.batch.ready_lanes()
            for lane in lanes:
                if self._advance_pending[lane]:
                    continue
                if not self._ready_q[lane].empty():
                    continue
                with self.stats.span("node.ready_build"):
                    rd = self.batch.ready(lane)
                self._advance_pending[lane] = True
                self._ready_q[lane].put(rd)

    def _handle(self, op: _Op):
        b = self.batch
        # a cancel event observed set before execution claims the CANCELED
        # transition on the waiter's behalf (the waiter may still be inside
        # its poll interval); then the PENDING->STARTED claim races any
        # concurrent try_cancel atomically. Either way: the reference's
        # select never picks the channel send once ctx.Done() fired — a
        # skipped message is not stepped at all.
        canceled = (
            op.cancel is not None and op.cancel.is_set() and op.try_cancel()
        )
        if canceled or not op.try_start():
            op.error = ErrCanceled()
            if op.done is not None:
                op.done.set()
            return
        try:
            with self.stats.span(f"node.{op.kind}"):
                self._execute(op, b)
        except Exception as e:  # surface to caller when waiting
            op.error = e
        finally:
            if op.done is not None:
                op.done.set()

    def _execute(self, op: _Op, b: RawNodeBatch):
        if op.kind == "tick":
            b.tick(op.lane)
        elif op.kind == "propose":
            b.propose(op.lane, op.payload)
        elif op.kind == "propose_cc":
            data, v2 = op.payload
            b.propose_conf_change(op.lane, data, v2=v2)
        elif op.kind == "step":
            b.step(op.lane, op.payload)
        elif op.kind == "advance":
            b.advance(op.lane)
            self._advance_pending[op.lane] = False
        elif op.kind == "campaign":
            b.campaign(op.lane)
        elif op.kind == "apply_cc":
            op.result = b.apply_conf_change(op.lane, op.payload)
        elif op.kind == "transfer":
            b.transfer_leadership(op.lane, op.payload)
        elif op.kind == "read_index":
            b.read_index(op.lane, op.payload)
        elif op.kind == "report_unreachable":
            b.report_unreachable(op.lane, op.payload)
        elif op.kind == "report_snapshot":
            peer, ok = op.payload
            b.report_snapshot(op.lane, peer, ok)
        elif op.kind == "status":
            op.result = b.status(op.lane)
        elif op.kind == "compact":
            idx, data = op.payload
            b.compact(op.lane, idx, data)

    def metrics_snapshot(self) -> dict:
        """Loop-thread op timings in the snapshot schema (no histogram) —
        register with a MetricsRegistry next to the engine/serve planes."""
        return self.stats.snapshot()

    def _submit(
        self, kind, lane, payload=None, wait=False, timeout=None, cancel=None
    ):
        """wait=True blocks like the reference's stepWait (node.go:502-545):
        `timeout` (seconds) is the ctx-deadline analog, `cancel` (a
        threading.Event) the ctx-cancellation analog. Ops whose
        cancellation fires before the loop reaches them are never applied."""
        # a deadline needs its own cancel event so the op is skipped (not
        # executed late) once the caller has given up on it
        if wait and timeout is not None and cancel is None:
            cancel = threading.Event()
        # the cancel event is honored even for fire-and-forget submissions:
        # the loop checks it before claiming the op (the documented
        # "canceled before the loop reaches it => never applied" guarantee
        # does not depend on anyone waiting)
        op = _Op(
            kind, lane, payload,
            threading.Event() if wait else None,
            cancel=cancel,
        )
        self._ops.put(op)
        if wait:
            # default: no deadline — first XLA compiles can take minutes;
            # the loop thread always sets done (or the host is stopped)
            import time as _time

            deadline = None if timeout is None else _time.monotonic() + timeout
            while not op.done.wait(timeout=0.05):
                if self._stop.is_set():
                    raise ErrStopped()
                if cancel is not None and cancel.is_set():
                    if not op.try_cancel():
                        # the loop already won the transition and is executing
                        # it (the reference's ctx race: the proposal proceeds);
                        # keep waiting for it to finish
                        continue
                    # we won: the loop is guaranteed to skip it
                    raise ErrCanceled()
                if deadline is not None and _time.monotonic() > deadline:
                    cancel.set()  # belt-and-braces for external observers
                    if not op.try_cancel():
                        continue  # already executing: let it finish
                    raise TimeoutError(f"{kind} timed out after {timeout}s")
            if op.error is not None:
                raise op.error
            return op.result
        return None


class Node:
    """Per-lane async handle (reference: node.go:132-243)."""

    def __init__(self, host: NodeHost, lane: int):
        self.host = host
        self.lane = lane

    def tick(self):
        self.host._submit("tick", self.lane)

    def campaign(self):
        self.host._submit("campaign", self.lane)

    def propose(
        self,
        data: bytes,
        wait: bool = True,
        timeout: float | None = None,
        cancel: threading.Event | None = None,
    ):
        """Blocking like the reference (node.go:469 Propose -> stepWait):
        returns once the proposal was stepped (raising ErrProposalDropped if
        refused), or raises TimeoutError / ErrCanceled / ErrStopped on the
        ctx-equivalent arms (node.go:502-545)."""
        self.host._submit(
            "propose", self.lane, data, wait=wait, timeout=timeout, cancel=cancel
        )

    def propose_conf_change(
        self,
        data: bytes,
        v2: bool = False,
        wait: bool = True,
        timeout: float | None = None,
        cancel: threading.Event | None = None,
    ):
        self.host._submit(
            "propose_cc", self.lane, (data, v2),
            wait=wait, timeout=timeout, cancel=cancel,
        )

    def step(
        self,
        msg: Message,
        wait: bool = False,
        timeout: float | None = None,
        cancel: threading.Event | None = None,
    ):
        """Non-blocking for network messages (reference node.Step); pass
        wait=True for the stepWait contract on local proposals."""
        if msg.type in LOCAL_MSGS and msg.frm not in (
            LOCAL_APPEND_THREAD,
            LOCAL_APPLY_THREAD,
        ):
            # reference: node.go:525-530 — local messages are silently
            # ignored by node.Step; here we reject loudly so misuse of the
            # tick/campaign/report_* APIs is visible. Storage-thread acks
            # (async-storage mode) pass, as in rawnode.go:108-125.
            raise ValueError("cannot step raft local message")
        self.host._submit(
            "step", self.lane, msg, wait=wait, timeout=timeout, cancel=cancel
        )

    def ready(self, timeout: float | None = None) -> Ready:
        """Blocking receive, like `<-n.Ready()` (reference: node.go:547)."""
        return self.host._ready_q[self.lane].get(timeout=timeout)

    def has_ready(self) -> bool:
        return not self.host._ready_q[self.lane].empty()

    def advance(self):
        self.host._submit("advance", self.lane)

    def apply_conf_change(self, cc):
        return self.host._submit("apply_cc", self.lane, cc, wait=True)

    def transfer_leadership(self, transferee: int):
        self.host._submit("transfer", self.lane, transferee)

    def read_index(self, ctx: int):
        self.host._submit("read_index", self.lane, ctx)

    def report_unreachable(self, peer: int):
        self.host._submit("report_unreachable", self.lane, peer)

    def report_snapshot(self, peer: int, ok: bool):
        self.host._submit("report_snapshot", self.lane, (peer, ok))

    def status(self) -> dict:
        return self.host._submit("status", self.lane, wait=True)

    def compact(self, to_index: int, data: bytes = b""):
        self.host._submit("compact", self.lane, (to_index, data), wait=True)

    def stop(self):
        self.host.stop()
