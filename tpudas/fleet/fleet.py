"""The multi-array round engine: N concurrent streams, one process.

Real DAS sites run several interrogators; :class:`FleetEngine`
schedules N :class:`tpudas.fleet.config.StreamSpec` round loops
(:mod:`tpudas.fleet.engine` runners) concurrently in one process, so
they share the jit/compile caches, one metrics registry, and one serve
plane instead of paying N cold processes.  Each stream keeps its OWN
durable state under ``root/<stream_id>/`` — carry, quarantine ledger,
pyramid, detect artifacts, ``health.json`` — written by exactly the
same runner code the single-stream drivers use, which is what makes
the acceptance claim checkable at all: a fleet member's folder is
byte-identical to the same stream run alone.

**Scheduling: deficit round-robin over due streams.**  The engine
keeps a virtual clock (seconds; ``sleep_fn`` is called with the wait
and the clock then advances by it, matching the drivers'
injected-sleep test idiom).  A stream is *due* when its jittered poll
interval (or retry backoff) has elapsed.  Each scheduling pass grants
every due stream a fixed service ``quantum`` of deficit; the stream
with the largest deficit runs ONE :meth:`step`, and the wall seconds
it actually consumed are charged back against its deficit.  A slow or
quarantine-storming spool therefore goes deeply negative and the
other due streams are served first until it earns its turn back — one
bad stream cannot starve the rest.  Deficit is capped at
``deficit_cap`` so an idle stream cannot hoard an unbounded burst.

**Fault isolation.**  A stream's transient/corrupt/resource failures
are retried by its own per-stream fault boundary exactly as before.  A
FATAL stream failure (config error, exhausted retries) **parks** that
stream — its terminal health snapshot is written, the error recorded
in the run summary, ``tpudas_fleet_streams_parked`` raised — and the
fleet keeps serving the others.  ``KeyboardInterrupt``/``SystemExit``
are not faults: they propagate and kill the whole fleet, which is the
process-crash model the crash-only design already resumes from
(``tools/crash_drill.py --streams N`` drills exactly this).

**Jitter.**  Streams default to ``default_poll_jitter`` (fraction of
the poll interval, stretched by a per-stream LCG seeded by the stream
id) so N co-located streams de-synchronize their spool scans instead
of thundering-herding the filesystem; a spec's explicit
``poll_jitter`` (or ``TPUDAS_POLL_JITTER``) wins.

**Batched scheduling (ISSUE 16).**  With ``batched=True`` (or
``TPUDAS_FLEET_BATCHED=1``) the scheduler becomes group-by-plan: due
streams whose memoized batch signature matches
(:class:`tpudas.fleet.batch.BatchGroupFormer`) are serviced as ONE
group — one thread per member runs its ordinary ``step()``, and the
members' device dispatches rendezvous in a
:class:`tpudas.fleet.batch.BatchStepExecutor` that stacks co-shaped
blocks into one device program (ragged channel packing; per-stream
outputs and carries byte-identical to solo execution).  A member that
faults mid-round drops out of its batch group — not the fleet — with
its carry sliced back out intact; it parks exactly as in solo
scheduling.  See FLEET.md "Batched scheduling" for the policy and
when to leave it off.

See FLEET.md for topology, directory layout, policy, and the runbook.
"""

from __future__ import annotations

import collections as _collections
import time as _time
from dataclasses import replace

from tpudas.fleet.config import StreamSpec
from tpudas.fleet.engine import StreamRunner, build_runner
from tpudas.obs.registry import get_registry
from tpudas.obs.trace import span
from tpudas.utils.logging import log_event
from tpudas.utils.profiling import Counters

__all__ = ["DEFAULT_POLL_JITTER", "FleetEngine", "run_fleet"]

# fleet default: up to +10% per-stream interval stretch — enough to
# spread N spool scans without distorting the cadence an operator set
DEFAULT_POLL_JITTER = 0.1

_QUANTUM_SEC = 0.25  # deficit granted per scheduling pass while due
_DEFICIT_CAP_SEC = 2.0  # max service burst an idle stream can bank
_SERVICE_LOG_MAX = 4096  # service_log entries kept (newest win)


class _FleetStream:
    """Per-stream scheduler state around one runner."""

    __slots__ = (
        "spec", "runner", "status", "error", "next_due", "deficit",
        "steps", "wall_seconds", "probe_due", "probe_interval",
        "probes", "unparks", "parked_at", "unparked_at",
    )

    def __init__(self, spec: StreamSpec, runner: StreamRunner | None):
        self.spec = spec
        self.runner = runner  # None when construction itself failed
        self.status = "active"  # active|terminated|max_rounds|parked
        self.error = None
        self.next_due = 0.0  # virtual seconds; 0 = poll immediately
        self.deficit = 0.0
        self.steps = 0
        self.wall_seconds = 0.0
        # unpark probe state (ISSUE 12): parked streams may re-probe
        # on a slow doubling schedule (mirrors the quarantine probe)
        self.probe_due = None  # virtual seconds; None = no probe
        self.probe_interval = None
        self.probes = 0
        self.unparks = 0
        # wall-clock park/unpark event times (ISSUE 13): surfaced in
        # health.json's `fleet` sub-object and the /fleet/healthz rollup
        self.parked_at = None
        self.unparked_at = None

    @property
    def stream_id(self) -> str:
        return str(self.spec.stream_id)


class FleetEngine:
    """Schedule N stream round loops in one process.

    Parameters
    ----------
    root:
        The fleet root; stream ``s`` writes under ``root/s`` unless its
        spec names an explicit ``output_folder``.
    specs:
        The :class:`StreamSpec` members.  ``stream_id`` must be unique.
    max_rounds:
        Per-stream poll cap (the drivers' ``max_rounds`` semantics: a
        stream stops after that many polls, clean-flushed).
    sleep_fn:
        Called with the seconds until the next stream is due when no
        stream is due now; the virtual clock then advances by that
        wait.  Tests inject a feeder exactly as with the drivers.
    quantum / deficit_cap:
        Deficit round-robin tuning (seconds of service).
    default_poll_jitter:
        Jitter fraction applied to specs that do not set their own.
    on_round:
        Optional ``on_round(stream_id, round, lfp)`` callback
        (lowpass streams only, matching the driver hook).
    unpark_probe:
        Seconds until a PARKED stream's first re-probe (None, the
        default, keeps parking terminal for the process lifetime —
        the pre-ISSUE-12 behavior).  When set, a parked stream is
        re-probed on a doubling-interval schedule (mirroring the
        quarantine probe policy): the probe rebuilds the runner from
        disk — crash-only, so a stream parked on a transient-looking
        fatal (disk briefly full, a config file mid-edit) rejoins the
        fleet where it left off.  A failed probe doubles the
        interval; after ``unpark_max_probes`` failures the park is
        terminal.  Successful unparks are counted
        (``tpudas_fleet_unparked_total``) and both transitions leave
        a ``fleet`` park/unpark event in the stream's health.json.
    batched:
        Group-by-plan batched scheduling (ISSUE 16): due streams with
        a matching batch signature are serviced together and their
        device steps stacked into one launch.  ``None`` (default)
        reads ``TPUDAS_FLEET_BATCHED`` (off unless ``1``).  Outputs,
        carries, pyramid, and detect artifacts are byte-identical to
        unbatched scheduling (tests/test_fleet_batch.py pins it);
        service ORDER within a round differs (group members run
        concurrently).
    """

    def __init__(
        self,
        root,
        specs,
        max_rounds=None,
        sleep_fn=_time.sleep,
        quantum: float = _QUANTUM_SEC,
        deficit_cap: float = _DEFICIT_CAP_SEC,
        default_poll_jitter: float = DEFAULT_POLL_JITTER,
        on_round=None,
        unpark_probe: float | None = None,
        unpark_max_probes: int = 6,
        batched: bool | None = None,
    ):
        import os

        specs = list(specs)
        if not specs:
            raise ValueError("FleetEngine needs at least one StreamSpec")
        ids = [str(s.stream_id) for s in specs]
        if len(set(ids)) != len(ids):
            dupes = sorted({i for i in ids if ids.count(i) > 1})
            raise ValueError(f"duplicate stream_id(s): {dupes}")
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self.max_rounds = max_rounds
        self.sleep_fn = sleep_fn
        self.quantum = float(quantum)
        self.deficit_cap = float(deficit_cap)
        self.unpark_probe = (
            None if unpark_probe is None else float(unpark_probe)
        )
        self.unpark_max_probes = int(unpark_max_probes)
        # ragged-batched scheduling (ISSUE 16): default OFF, opt in per
        # engine or fleet-wide via env (the crash drill's --batched leg
        # and the bench A/B flip it this way)
        if batched is None:
            batched = os.environ.get("TPUDAS_FLEET_BATCHED", "0") == "1"
        self.batched = bool(batched)
        from tpudas.fleet.batch import BatchGroupFormer

        self._former = BatchGroupFormer()
        self._on_round = on_round
        self.now = 0.0  # virtual seconds since run start
        self.sched_seconds = 0.0  # wall spent in scheduler bookkeeping
        # (stream_id, status, wall) per step, bounded so a months-long
        # fleet run cannot grow it without limit (the bench reads it)
        self.service_log = _collections.deque(maxlen=_SERVICE_LOG_MAX)
        # N same-geometry arrays in one process share jax's in-process
        # jit cache by construction; the persistent cache makes fleet
        # restarts warm-start across processes too
        from tpudas.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        reg = get_registry()
        self.streams: dict = {}
        for spec in specs:
            # precedence: spec's explicit poll_jitter > TPUDAS_POLL_JITTER
            # (resolved inside the runner) > the fleet default
            if (
                spec.config.poll_jitter is None
                and not os.environ.get("TPUDAS_POLL_JITTER", "")
            ):
                spec = replace(
                    spec,
                    config=replace(
                        spec.config, poll_jitter=default_poll_jitter
                    ),
                )
            # runner construction (folder creation, startup audit,
            # config coercion) gets the same per-stream fault boundary
            # as step(): a stream that cannot even build is PARKED, the
            # fleet still serves the others
            try:
                runner = self._build_runner(spec)
            except Exception as exc:
                s = _FleetStream(spec, None)
                self.streams[s.stream_id] = s
                self._park(s, exc)
                continue
            self.streams[str(spec.stream_id)] = _FleetStream(spec, runner)
        reg.gauge(
            "tpudas_fleet_streams",
            "streams configured in the fleet engine",
        ).set(len(self.streams))
        self._state_gauges()

    def _build_runner(self, spec: StreamSpec) -> StreamRunner:
        on_round = self._on_round
        return build_runner(
            spec,
            root=self.root,
            counters=Counters(),
            on_round=(
                None if on_round is None else (
                    lambda rnd, lfp, _sid=str(spec.stream_id): (
                        on_round(_sid, rnd, lfp)
                    )
                )
            ),
        )

    # -- scheduling ------------------------------------------------------
    def _state_gauges(self) -> None:
        reg = get_registry()
        states = [s.status for s in self.streams.values()]
        reg.gauge(
            "tpudas_fleet_streams_active",
            "fleet streams still polling",
        ).set(sum(1 for s in states if s == "active"))
        reg.gauge(
            "tpudas_fleet_streams_parked",
            "fleet streams parked after a fatal per-stream failure",
        ).set(sum(1 for s in states if s == "parked"))

    def _active(self):
        return [s for s in self.streams.values() if s.status == "active"]

    def _pick(self, due):
        """Deficit round-robin: grant every due stream a quantum, then
        serve the one owed the most (ties: earliest due, then spec
        order — both deterministic)."""
        for s in due:
            s.deficit = min(s.deficit + self.quantum, self.deficit_cap)
        return max(due, key=lambda s: (s.deficit, -s.next_due))

    def _finish_stream(self, s: _FleetStream, status: str) -> None:
        s.runner.finish()
        s.status = status
        log_event(
            "fleet_stream_done",
            stream=s.stream_id,
            status=status,
            rounds=s.runner.rounds,
            polls=s.runner.polls,
        )
        self._state_gauges()

    def _park(self, s: _FleetStream, exc: BaseException) -> None:
        self._former.invalidate(s.stream_id)
        s.status = "parked"
        s.error = f"{type(exc).__name__}: {str(exc)[:300]}"
        s.parked_at = _time.time()
        # schedule the unpark re-probe (doubling interval, bounded
        # attempts — the quarantine probe policy, stream-sized)
        if self.unpark_probe is not None and (
            s.probes < self.unpark_max_probes
        ):
            s.probe_interval = (
                self.unpark_probe if s.probe_interval is None
                else s.probe_interval * 2.0
            )
            s.probe_due = self.now + s.probe_interval
        else:
            s.probe_due = None
        if s.runner is not None:
            health = getattr(s.runner, "edge_health", None)
            if health is not None:
                health.extra["fleet"] = {
                    "event": "parked",
                    "parked_at": s.parked_at,
                    "unparked_at": s.unparked_at,
                    "unparks": s.unparks,
                    "error": s.error,
                }
            try:
                s.runner.record_fatal(exc)
            except Exception as exc2:
                log_event(
                    "fleet_record_fatal_failed",
                    stream=s.stream_id,
                    error=f"{type(exc2).__name__}: {str(exc2)[:200]}",
                )
        get_registry().counter(
            "tpudas_fleet_parked_total",
            "streams parked by a fatal per-stream failure (the fleet "
            "keeps serving the others)",
        ).inc()
        log_event(
            "fleet_stream_parked", stream=s.stream_id, error=s.error
        )
        self._state_gauges()

    def _try_unpark(self, s: _FleetStream) -> bool:
        """One unpark probe: rebuild the runner from disk (crash-only
        resume — carry/ledger/pyramid say where to continue).  A
        failed rebuild doubles the probe interval; success puts the
        stream back in the rotation immediately."""
        s.probes += 1
        try:
            runner = self._build_runner(s.spec)
        except Exception as exc:
            s.error = f"{type(exc).__name__}: {str(exc)[:300]}"
            if s.probes >= self.unpark_max_probes:
                s.probe_due = None  # terminal: probes exhausted
            else:
                s.probe_interval *= 2.0
                s.probe_due = self.now + s.probe_interval
            log_event(
                "fleet_unpark_probe_failed",
                stream=s.stream_id,
                probe=s.probes,
                error=s.error,
            )
            return False
        s.runner = runner
        self._former.invalidate(s.stream_id)
        s.status = "active"
        s.error = None
        s.next_due = self.now
        s.deficit = 0.0
        s.probe_due = None
        s.unparks += 1
        s.unparked_at = _time.time()
        health = getattr(runner, "edge_health", None)
        if health is not None:
            health.extra["fleet"] = {
                "event": "unparked",
                "parked_at": s.parked_at,
                "unparked_at": s.unparked_at,
                "unparks": s.unparks,
                "probes": s.probes,
            }
        get_registry().counter(
            "tpudas_fleet_unparked_total",
            "parked streams that rejoined the fleet via the unpark "
            "re-probe",
        ).inc()
        log_event(
            "fleet_stream_unparked", stream=s.stream_id, probe=s.probes
        )
        self._state_gauges()
        return True

    def _account_step(self, s, res, wall: float, reg) -> None:
        """Post-step bookkeeping shared by solo and batched service:
        step counters, service log, terminate/max_rounds transitions,
        next-due scheduling.  The caller has already charged ``wall``
        against the stream's deficit."""
        s.steps += 1
        s.wall_seconds += wall
        self.service_log.append((s.stream_id, res.status, wall))
        reg.counter(
            "tpudas_fleet_steps_total",
            "runner steps executed by the fleet scheduler",
            labelnames=("stream", "status"),
        ).inc(stream=s.stream_id, status=res.status)
        reg.histogram(
            "tpudas_fleet_step_seconds",
            "wall seconds of one scheduled runner step",
            labelnames=("stream",),
        ).observe(wall, stream=s.stream_id)
        if res.status == "terminate":
            self._finish_stream(s, "terminated")
        elif (
            self.max_rounds is not None
            and s.runner.polls >= self.max_rounds
        ):
            self._finish_stream(s, "max_rounds")
        else:
            s.next_due = self.now + res.delay

    def _batch_group(self, s, due):
        """The batch group for the picked stream: every due stream
        whose memoized signature matches (ISSUE 16 group-by-plan).
        ``None`` when the stream must run solo (no signature, or no
        due peer shares it)."""
        sig = self._former.signature(s.stream_id, s.runner)
        if sig is None:
            return None
        group = [
            o for o in due
            if o is s
            or self._former.signature(o.stream_id, o.runner) == sig
        ]
        return group if len(group) >= 2 else None

    def _service_group(self, group, reg) -> None:
        """Service one batch group: one thread per member runs its
        ordinary ``step()`` with the shared
        :class:`~tpudas.fleet.batch.BatchStepExecutor` installed, so
        co-shaped device dispatches stack into one launch.  Each
        member's wall (including rendezvous waits) is charged to its
        own deficit; park/terminate handling per member is identical
        to solo service.  ``KeyboardInterrupt``/``SystemExit`` from a
        member are re-raised after the group joins — the whole-fleet
        crash model, same as solo scheduling (the other members'
        completed rounds are already durable; crash-only resume picks
        them up)."""
        import threading

        from tpudas.fleet.batch import BatchStepExecutor

        ex = BatchStepExecutor([s.stream_id for s in group])
        outcomes: dict = {}

        def _run(s):
            ex.bind(s.stream_id)
            s.runner._batch_executor = ex
            t0 = _time.perf_counter()
            try:
                with span("fleet.step", stream=s.stream_id):
                    res = s.runner.step()
                outcomes[s.stream_id] = (
                    "ok", res, _time.perf_counter() - t0
                )
            except BaseException as exc:
                outcomes[s.stream_id] = (
                    "raise", exc, _time.perf_counter() - t0
                )
            finally:
                s.runner._batch_executor = None
                ex.leave(s.stream_id)

        with span("fleet.batch", streams=len(group)):
            threads = [
                threading.Thread(
                    target=_run, args=(s,),
                    name=f"fleet-batch-{s.stream_id}", daemon=True,
                )
                for s in group
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        reg.counter(
            "tpudas_fleet_batch_groups_total",
            "batch groups serviced by the group-by-plan scheduler",
        ).inc()
        reg.counter(
            "tpudas_fleet_batch_members_total",
            "stream steps serviced inside a batch group",
        ).inc(len(group))
        fatal = None
        for s in group:
            kind, val, wall = outcomes[s.stream_id]
            s.deficit -= wall
            if kind == "raise":
                s.wall_seconds += wall
                self.service_log.append((s.stream_id, "fatal", wall))
                if isinstance(val, Exception):
                    # a faulted member drops out of its batch group —
                    # not the fleet; its carry was sliced back out by
                    # the last completed dispatch
                    self._park(s, val)
                elif fatal is None:
                    fatal = val
                continue
            self._account_step(s, val, wall, reg)
        if fatal is not None:
            raise fatal

    def run(self) -> dict:
        """Serve every stream until it terminates (spool stopped
        growing), hits the ``max_rounds`` poll cap, or parks on a
        fatal failure.  Returns the run summary (per-stream status,
        rounds, polls, realtime factor, head lag, error)."""
        reg = get_registry()
        t_run0 = _time.perf_counter()
        with span("fleet.run", streams=len(self.streams)):
            while True:
                t_sched = _time.perf_counter()
                active = self._active()
                probing = (
                    [
                        s for s in self.streams.values()
                        if s.status == "parked" and s.probe_due is not None
                    ]
                    if self.unpark_probe is not None else []
                )
                if not active and not probing:
                    self.sched_seconds += _time.perf_counter() - t_sched
                    break
                probe_due = [s for s in probing if s.probe_due <= self.now]
                if probe_due:
                    # probes are cheap and rare: serve them before the
                    # deficit rotation (an unparked stream then joins
                    # the due set on this same pass)
                    self.sched_seconds += _time.perf_counter() - t_sched
                    for s in probe_due:
                        self._try_unpark(s)
                    continue
                due = [s for s in active if s.next_due <= self.now]
                if not due:
                    wait = min(
                        [s.next_due for s in active]
                        + [s.probe_due for s in probing]
                    ) - self.now
                    self.sched_seconds += _time.perf_counter() - t_sched
                    self.sleep_fn(max(wait, 0.0))
                    self.now += max(wait, 0.0)
                    continue
                s = self._pick(due)
                group = (
                    self._batch_group(s, due) if self.batched else None
                )
                self.sched_seconds += _time.perf_counter() - t_sched
                if group is not None:
                    self._service_group(group, reg)
                    continue
                t0 = _time.perf_counter()
                try:
                    with span("fleet.step", stream=s.stream_id):
                        res = s.runner.step()
                except Exception as exc:
                    wall = _time.perf_counter() - t0
                    s.deficit -= wall
                    s.wall_seconds += wall
                    self.service_log.append(
                        (s.stream_id, "fatal", wall)
                    )
                    self._park(s, exc)
                    continue
                wall = _time.perf_counter() - t0
                s.deficit -= wall
                self._account_step(s, res, wall, reg)
        wall_total = _time.perf_counter() - t_run0
        reg.counter(
            "tpudas_fleet_sched_seconds_total",
            "wall seconds spent in fleet scheduler bookkeeping "
            "(due-set scan, deficit round-robin pick)",
        ).inc(self.sched_seconds)
        return self.summary(wall_total)

    def summary(self, wall_seconds: float | None = None) -> dict:
        streams = {}
        for sid, s in self.streams.items():
            r = s.runner  # None when the stream parked at build time
            streams[sid] = {
                "status": s.status,
                "rounds": 0 if r is None else r.rounds,
                "polls": 0 if r is None else r.polls,
                "steps": s.steps,
                "wall_seconds": round(s.wall_seconds, 4),
                "realtime_factor": round(
                    getattr(
                        getattr(r, "counters", None),
                        "realtime_factor", 0.0,
                    ),
                    3,
                ),
                "head_lag_seconds": getattr(r, "head_lag", None),
                "unparks": s.unparks,
                "parked_at": s.parked_at,
                "unparked_at": s.unparked_at,
                "error": s.error,
            }
        return {
            "streams": streams,
            "rounds_total": sum(
                s.runner.rounds
                for s in self.streams.values()
                if s.runner is not None
            ),
            "parked": sorted(
                sid for sid, s in self.streams.items()
                if s.status == "parked"
            ),
            "unparked_total": sum(
                s.unparks for s in self.streams.values()
            ),
            "sched_seconds": round(self.sched_seconds, 4),
            "wall_seconds": (
                None if wall_seconds is None else round(wall_seconds, 4)
            ),
        }


def run_fleet(root, specs, **kwargs) -> dict:
    """Build a :class:`FleetEngine` over ``specs`` and run it to
    completion; returns the run summary."""
    return FleetEngine(root, specs, **kwargs).run()
