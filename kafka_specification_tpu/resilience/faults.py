"""Deterministic fault injection (`KSPEC_FAULT` env grammar).

The engines call into an active `FaultPlan` at their recovery-relevant
boundaries, so every recovery path (crash -> resume, corrupt checkpoint ->
fallback, transient backend error -> retry, escalated-compile OOM ->
uniform fallback) is drivable from a tier-1 CPU test or a supervised
production rehearsal — no real hardware failure needed.

Grammar (comma-separated specs in `KSPEC_FAULT` or `--fault`):

    crash@level:N             raise InjectedCrash at the level-N boundary
    crash@ckpt:N              raise InjectedCrash mid-checkpoint-write at
                              level N (after the tmp write, BEFORE the
                              atomic promote — the torn-write rehearsal)
    crash@merge:N             raise InjectedCrash mid-way through the Nth
                              disk-run merge of this process (merged tmp
                              written, BEFORE the atomic promote — the
                              disk tier's torn-write rehearsal,
                              storage/tiered.py).  Like crash@ckpt, meant
                              for in-process tests: N counts merges per
                              process, so a supervised restart that
                              re-reaches the Nth merge would re-fire
    corrupt_ckpt              corrupt the newest checkpoint right after its
                              first write (checksum-fallback rehearsal)
    corrupt_ckpt@ckpt:N       same, after the write at level N
    compile_oom               the next escalated (per-action-tuple) chunk
                              step raises an LLVM-OOM-shaped error once
                              (the reproducible wide-product XLA:CPU
                              failure)
    transient_device_err:N    the next N chunk/exchange step executions
                              raise a transient-classified backend error

Resource faults (the out-of-things failure family — resilience.resources;
every one must end in a typed RESOURCE_EXHAUSTED clean exit whose on-disk
state still passes `cli verify-checkpoint`):

    enospc@spill:N            the Nth spill-run write of this process
                              raises OSError(ENOSPC) after the tmp write,
                              before the atomic promote (the full-disk
                              rehearsal for storage/tiered.py; like
                              crash@merge, N is a per-process ordinal)
    enospc@merge:N            same, mid-way through the Nth disk-run merge
    enospc@ckpt:N             OSError(ENOSPC) mid-checkpoint-write at
                              level N (after the tmp write, before the
                              atomic promote — previous generations stay
                              intact and verifiable)
    enospc@plog:N             OSError(ENOSPC) publishing the level-N
                              parent-log segment
    stall@level:N             the per-level deadline watchdog reports
                              level N as stalled (the silent-stall
                              rehearsal; fires at the level-N boundary
                              once the run is durably past it)

Bit-flip faults (the silent-data-corruption family — resilience.integrity;
every one must be *detected* by the always-on integrity layer and end in a
typed INTEGRITY_VIOLATION exit 76 whose on-disk state resumes from the
newest chain-verified checkpoint generation):

    flip@frontier:N           flip one bit in the in-memory frontier
                              buffer at the level-N boundary (detected by
                              the level digest chain's frontier verify)
    flip@fpset:N              flip one bit in the visited-set dump taken
                              for the first checkpoint past level N
                              (detected by the save-time cumulative-digest
                              self-check BEFORE the write — corruption
                              never enters a checkpoint)
    flip@exchange:N           the level-N sharded exchange framing check
                              observes a corrupted payload digest on the
                              scoped shard (like stall@level, the fault
                              drives the detector's observation; the
                              in-jit sent/received digests are what a
                              real ICI bit flip would desync)
    flip@spill:N              flip bytes in the Nth spill-run file of this
                              process after its atomic promote (detected
                              by the read-side CRC verify on the run's
                              first lookup; N is a per-process ordinal —
                              in-process test use, like crash@merge)
    flip@ckpt:N               flip the `levels` array of the first
                              checkpoint written past level N BEFORE its
                              CRC manifest is built — a CRC-consistent
                              corrupted generation (detected by the
                              post-save chain read-back, by the resume
                              path's chain validator, and by the offline
                              `cli verify-checkpoint`)

    Level-keyed flip sites (frontier/fpset/exchange/ckpt) use the same
    checkpoint deferral as crash@level — on a checkpointing run they fire
    only once a generation at or past N exists, so a supervised restart
    resumes at or past N, the resume-depth relief applies, and the
    restart converges instead of flip-looping.

Shard scoping (the distributed engine's fault surface): any `@` fault may
carry a `shard<d>:` scope immediately after the `@`, and the bare faults
accept `@shard<d>` — the fault then fires only on the process that hosts
shard `d`'s device (`FaultPlan.set_local_shards`, wired by
`parallel/sharded.py` from the mesh's device->process map):

    crash@shard2:level:N          kill exactly the process hosting shard 2
                                  at the level-N boundary (its peers block
                                  in the next collective until the fleet
                                  supervisor tears the job down)
    crash@shard2:ckpt:N           torn-write rehearsal on one shard's host
    corrupt_ckpt@shard1           corrupt a checkpoint written by shard
    corrupt_ckpt@shard1:ckpt:N    1's host (its per-host part file, in a
                                  multi-process job)
    transient_device_err@shard0:N transient errors on shard 0's host only

In a single-process run every shard is local, so shard-scoped faults
degenerate to their unscoped forms — which is exactly what lets the whole
matrix run in tier-1 on the virtual CPU mesh.  Engines that never call
`set_local_shards` (the single-device engine) treat every scope as local
for the same reason.

Crash faults fire only when the run *started* below the target level
(`FaultPlan.set_start_depth` is called by the engines after a checkpoint
resume), and on a checkpointing run a `crash@level:N` additionally defers
until a checkpoint at or past level N exists — so a supervised restart
always resumes at or past the target and converges instead of
crash-looping, for any `checkpoint_every`.  `crash@ckpt:N` is the
exception — a resume from the previous good generation starts below N
again and would re-fire; it is meant for in-process torn-write tests, not
supervised runs.

Host faults (the cross-host service chaos family — service/router.py +
service/fleet.py; each host of a routed fleet is an isolated service dir
whose daemons run with `KSPEC_HOST_INSTANCE=<i>`, wired to
`FaultPlan.set_host`; one composed plan string can then drive a whole
multi-host drill, with each fault firing only on its targeted host):

    kill@host<i>:N            kill host i's serving daemon while it
                              handles its Nth job (before any verdict) —
                              the whole-host-death rehearsal when the
                              host runs one daemon.  Durable once per
                              service dir, like crash@daemon, so a
                              restarted host converges
    partition@host<i>[:N]     host i loses the shared state-cache
                              namespace for its next N jobs (default 1):
                              lookups degrade to typed `cache-fallback`
                              cold runs, publishes are DEFERRED and
                              re-published when the partition heals —
                              never a torn or unverified cross-host read
    skew@host<i>:SECS         shift host i's wall clock by SECS (may be
                              negative) in every timestamp it writes
                              into cross-host-visible metadata (claim
                              leases, heartbeats) — the drifted-clock
                              rehearsal behind the KSPEC_CLOCK_SKEW
                              allowance in lease expiry and router
                              heartbeat freshness

Budgeted faults (`compile_oom`, `transient_device_err:N`) are consumed
in-process and do not persist across restarts.
"""

from __future__ import annotations

import errno
import os
from dataclasses import dataclass
from typing import Optional

ENV_VAR = "KSPEC_FAULT"

# markers chosen so retry.classify() routes the injected error down the
# same branch a real backend error of that family would take
TRANSIENT_MARKER = "DATA_LOSS: injected transient device error (KSPEC_FAULT)"
OOM_MARKER = "LLVM ERROR: out of memory (injected by KSPEC_FAULT=compile_oom)"


class InjectedFault(RuntimeError):
    """Base class for all deliberately injected failures."""


class InjectedCrash(InjectedFault):
    """An injected hard crash (the process is expected to die)."""


#: THE single registry of injectable sites — the parser validates against
#: it and `cli faults --list` renders it, so a new fault family cannot be
#: added without becoming enumerable and parse-checked at the same time.
#: kind -> (valid sites (None = bare fault), grammar form, description)
FAULT_REGISTRY = (
    ("crash", ("level", "ckpt", "merge", "daemon"),
     "crash@level|ckpt|merge:N | crash@daemon<i>:N",
     "raise InjectedCrash at the level-N boundary / mid-checkpoint-write "
     "(tmp written, pre-promote) / mid-way through the Nth disk-run merge; "
     "the daemon<i> form kills serving-daemon instance i while it handles "
     "its Nth job (claims stay leased; a sibling's janitor requeues them "
     "and the verdict still publishes exactly once — service/fleet.py).  "
     "Daemon-scoped faults fire once per SERVICE DIR (durable "
     "fired-marker), so a fleet-restarted daemon converges instead of "
     "crash-looping — the crash@level checkpoint-deferral rule's twin"),
    ("corrupt_ckpt", ("ckpt",), "corrupt_ckpt[@ckpt:N]",
     "corrupt the newest checkpoint right after its write (checksum-"
     "fallback rehearsal); bytes flipped AFTER the CRC manifest, so the "
     "zip/manifest checks catch it on load"),
    ("compile_oom", None, "compile_oom",
     "the next escalated chunk step raises an LLVM-OOM-shaped error once "
     "(degrades fused/adaptive paths to the uniform fallback)"),
    ("transient_device_err", None, "transient_device_err:N",
     "the next N chunk/exchange steps raise a transient-classified "
     "backend error (bounded-backoff retry rehearsal)"),
    ("enospc", ("spill", "ckpt", "merge", "plog", "cache"),
     "enospc@spill|ckpt|merge|plog|cache:N",
     "OSError(ENOSPC) at the writer's pre-promote point (typed "
     "RESOURCE_EXHAUSTED exit 75; state stays verifiable).  The cache "
     "site is the Nth state-space-cache publish of this process "
     "(service/state_cache.py): publication aborts cleanly with a "
     "cache-fallback event, the job's verdict is untouched"),
    ("stall", ("level", "daemon"), "stall@level:N | stall@daemon<i>",
     "the per-level deadline watchdog reports level N stalled (typed "
     "exit 75); the daemon<i> form wedges serving-daemon instance i "
     "after its next claim sweep — heartbeat and lease renewal freeze, "
     "so the fleet supervisor stall-kills it and a sibling's janitor "
     "takes its leased claims over at lease expiry"),
    ("flip", ("frontier", "fpset", "exchange", "spill", "ckpt", "cache"),
     "flip@frontier|fpset|exchange|spill|ckpt|cache:N",
     "silent bit-flip at the named state surface (typed "
     "INTEGRITY_VIOLATION exit 76; detected by the digest-chain / "
     "framing / read-side-CRC layer — resilience.integrity).  The cache "
     "site flips bytes in the Nth published state-space-cache artifact "
     "of this process AFTER its promote: the next lookup's chain/CRC "
     "verification rejects it with a cache-fallback event and the check "
     "degrades to a cold run — never a wrong verdict"),
    ("kill", ("host",), "kill@host<i>:N",
     "kill host i's serving daemon while it handles its Nth job, before "
     "any verdict is derived (the whole-host-death rehearsal of the "
     "routed fleet — service/router.py detects the stale heartbeats and "
     "re-routes the host's pending jobs; its leased claims come back via "
     "the janitor takeover protocol at lease expiry).  Fires once per "
     "SERVICE DIR (durable fired-marker), so a restarted host converges; "
     "hosts are scoped by KSPEC_HOST_INSTANCE, so one composed plan "
     "string drives a whole multi-host drill"),
    ("partition", ("host",), "partition@host<i>[:N]",
     "host i loses the shared state-space-cache namespace for its next N "
     "jobs (default 1): every lookup in the window degrades to a typed "
     "cache-fallback cold run (reason 'partition') and every publish is "
     "DEFERRED, then re-published when the partition heals — verdicts "
     "are untouched and the federation never serves a torn read.  "
     "Durable once per service dir, like kill@host"),
    ("skew", ("host",), "skew@host<i>:SECS",
     "shift host i's wall clock by SECS (float, may be negative) in "
     "every timestamp it writes into cross-host-visible metadata — "
     "claim-lease stamps and heartbeat records — rehearsing a fleet "
     "member with a drifted clock.  The KSPEC_CLOCK_SKEW allowance in "
     "lease expiry (service/queue.py) and router heartbeat freshness "
     "(service/router.py) is what keeps a skewed-but-live host's claims "
     "from being stolen; persistent for the process lifetime"),
)

_SITES_BY_KIND = {k: sites for k, sites, _g, _d in FAULT_REGISTRY}


def list_faults() -> list:
    """[{kind, grammar, description, scopeable}] for `cli faults --list`
    (every fault composes with a `shard<d>:` scope)."""
    return [
        {"kind": k, "grammar": g, "sites": list(sites or ()),
         "description": d, "scopeable": True}
        for k, sites, g, d in FAULT_REGISTRY
    ]


@dataclass
class _Spec:
    kind: str  # crash | corrupt_ckpt | compile_oom | transient_device_err
    point: Optional[str]  # level | ckpt | None
    arg: Optional[float]  # level/ordinal (int) or seconds (skew) — None =
    # first
    budget: int  # remaining firings
    shard: Optional[int] = None  # fire only on this shard's host process
    instance: Optional[int] = None  # fire only on this daemon instance
    host: Optional[int] = None  # fire only on this service host


def _split_shard(rest: str, tok: str):
    """Peel an optional `shard<d>:`/`shard<d>` scope off `rest`."""
    if not rest.startswith("shard"):
        return None, rest
    head, _, tail = rest.partition(":")
    try:
        shard = int(head[len("shard"):])
    except ValueError:
        raise ValueError(
            f"fault {tok!r}: shard scope must be 'shard<index>', got {head!r}"
        )
    if shard < 0:
        raise ValueError(f"fault {tok!r}: shard index must be >= 0")
    return shard, tail


def _parse_token(tok: str) -> _Spec:
    if "@" in tok:
        name, _, rest = tok.partition("@")
        shard, rest = _split_shard(rest, tok)
        if name == "corrupt_ckpt" and shard is not None and not rest:
            return _Spec("corrupt_ckpt", "ckpt", None, 1, shard)
        if name == "transient_device_err" and shard is not None:
            if rest:
                try:
                    budget = int(rest)
                except ValueError:
                    raise ValueError(
                        f"fault {tok!r}: budget must be an integer"
                    )
            else:
                budget = 1
            return _Spec("transient_device_err", None, None, budget, shard)
        if name == "compile_oom" and shard is not None and not rest:
            return _Spec("compile_oom", None, None, 1, shard)
        point, _, arg = rest.partition(":")
        if point.startswith("daemon") and name in ("crash", "stall"):
            # serving-daemon instance scope (service/fleet.py): the
            # instance index is part of the site token, like shard<d>
            try:
                inst = int(point[len("daemon"):])
            except ValueError:
                raise ValueError(
                    f"fault {tok!r}: daemon scope must be 'daemon<index>', "
                    f"got {point!r}"
                )
            if inst < 0:
                raise ValueError(
                    f"fault {tok!r}: daemon index must be >= 0"
                )
            if name == "stall":
                if arg:
                    raise ValueError(
                        f"fault {tok!r}: stall@daemon<i> takes no ':N' "
                        "(the daemon wedges at its next claim sweep)"
                    )
                return _Spec("stall", "daemon", None, 1, instance=inst)
            try:
                nth = int(arg)
            except ValueError:
                raise ValueError(
                    f"fault {tok!r}: crash@daemon<i>:N needs an integer "
                    "job ordinal N"
                )
            if nth < 1:
                raise ValueError(f"fault {tok!r}: job ordinal must be >= 1")
            return _Spec("crash", "daemon", nth, 1, instance=inst)
        if point.startswith("host") and name in ("kill", "partition",
                                                 "skew"):
            # service-host scope (service/router.py): the host index is
            # part of the site token, like daemon<i> — the plan string is
            # shared by every host of the routed fleet and each fault
            # fires only on its target (KSPEC_HOST_INSTANCE -> set_host)
            try:
                host = int(point[len("host"):])
            except ValueError:
                raise ValueError(
                    f"fault {tok!r}: host scope must be 'host<index>', "
                    f"got {point!r}"
                )
            if host < 0:
                raise ValueError(f"fault {tok!r}: host index must be >= 0")
            if name == "kill":
                try:
                    nth = int(arg)
                except ValueError:
                    raise ValueError(
                        f"fault {tok!r}: kill@host<i>:N needs an integer "
                        "job ordinal N"
                    )
                if nth < 1:
                    raise ValueError(
                        f"fault {tok!r}: job ordinal must be >= 1"
                    )
                return _Spec("kill", "host", nth, 1, host=host)
            if name == "partition":
                if arg:
                    try:
                        njobs = int(arg)
                    except ValueError:
                        raise ValueError(
                            f"fault {tok!r}: partition@host<i>:N needs an "
                            "integer job count N"
                        )
                    if njobs < 1:
                        raise ValueError(
                            f"fault {tok!r}: job count must be >= 1"
                        )
                else:
                    njobs = 1
                return _Spec("partition", "host", njobs, 1, host=host)
            try:
                secs = float(arg)
            except ValueError:
                raise ValueError(
                    f"fault {tok!r}: skew@host<i>:SECS needs a number of "
                    "seconds (float, may be negative)"
                )
            if secs == 0.0:
                raise ValueError(
                    f"fault {tok!r}: a zero skew rehearses nothing — "
                    "give a nonzero SECS"
                )
            return _Spec("skew", "host", secs, 1, host=host)
        if not arg:
            raise ValueError(f"fault {tok!r}: '@{point}' needs ':<level>'")
        try:
            level = int(arg)
        except ValueError:
            raise ValueError(f"fault {tok!r}: level must be an integer")
        if level < 1:
            # crash faults fire only when the run STARTED below the target
            # level (start_depth < N), so level 0 could never fire — reject
            # it instead of silently rehearsing nothing
            raise ValueError(f"fault {tok!r}: level must be >= 1")
        if name in _SITES_BY_KIND and _SITES_BY_KIND[name]:
            if point in _SITES_BY_KIND[name]:
                return _Spec(name, point, level, 1, shard)
            # a typo'd SITE must be as loud as a typo'd kind: a silently
            # no-op'd `crash@lvl:3` would report the drill as passed
            raise ValueError(
                f"fault {tok!r}: unknown site {point!r} for {name!r} "
                f"(valid sites: {', '.join(_SITES_BY_KIND[name])}; "
                f"run `cli faults --list` for the full grammar)"
            )
        raise ValueError(
            f"unknown fault {tok!r} (known kinds: "
            f"{', '.join(k for k, *_ in FAULT_REGISTRY)}; run "
            f"`cli faults --list` for the full grammar)"
        )
    name, _, count = tok.partition(":")
    if name == "corrupt_ckpt":
        if count:
            raise ValueError(f"fault {tok!r}: use corrupt_ckpt@ckpt:<level>")
        return _Spec("corrupt_ckpt", "ckpt", None, 1)
    if name == "compile_oom":
        return _Spec("compile_oom", None, None, int(count) if count else 1)
    if name == "transient_device_err":
        return _Spec(
            "transient_device_err", None, None, int(count) if count else 1
        )
    raise ValueError(
        f"unknown fault {tok!r} (grammar: "
        + ", ".join(g for _k, _s, g, _d in FAULT_REGISTRY)
        + "; each '@'-scopeable as crash@shard<d>:level:N / "
        "corrupt_ckpt@shard<d> / transient_device_err@shard<d>:N; run "
        "`cli faults --list` for descriptions)"
    )


class FaultPlan:
    """A parsed set of faults plus their remaining budgets.

    Engines construct one per run via `FaultPlan.from_env()`; an unset env
    yields an empty plan whose hooks are all no-ops.
    """

    def __init__(self, spec: str = ""):
        self.spec = spec or ""
        self.start_depth = 0
        # None = no topology wired: every shard scope counts as local
        # (single-process runs, and the single-device engine)
        self.local_shards: Optional[frozenset] = None
        # which serving-daemon instance this process is (set_instance,
        # wired by service/daemon.py from KSPEC_DAEMON_INSTANCE); daemon-
        # scoped faults fire only on an exact match — None never fires,
        # so engine-side plans carrying daemon faults are inert there
        self.instance: Optional[int] = None
        # which service host this process serves (set_host, wired by
        # service/daemon.py from KSPEC_HOST_INSTANCE); host-scoped faults
        # fire only on an exact match — same contract as `instance`
        self.host: Optional[int] = None
        self.specs = [
            _parse_token(t.strip())
            for t in self.spec.split(",")
            if t.strip()
        ]

    @classmethod
    def from_env(cls, env_var: str = ENV_VAR) -> "FaultPlan":
        return cls(os.environ.get(env_var, ""))

    def __bool__(self) -> bool:
        return bool(self.specs)

    def set_start_depth(self, depth: int) -> None:
        """Record the depth a resumed run starts from: crash faults at or
        below it are considered already-fired (restart convergence)."""
        self.start_depth = int(depth)

    def set_instance(self, instance: int) -> None:
        """Record which serving-daemon instance this process is
        (service/fleet.py launches each `cli serve` child with
        KSPEC_DAEMON_INSTANCE=i).  `crash@daemon<i>:N` / `stall@daemon<i>`
        then fire only in the targeted instance's process — its fleet
        siblings sail past, which is exactly the one-daemon-died /
        one-daemon-wedged failure the fleet supervisor exists to catch."""
        self.instance = int(instance)

    def _instance_match(self, s: _Spec) -> bool:
        return (
            s.instance is not None
            and self.instance is not None
            and s.instance == self.instance
        )

    def daemon_crash(self, lo: int, hi: Optional[int] = None) -> None:
        """Raise InjectedCrash if a `crash@daemon<i>:N` fault targets this
        daemon instance and job ordinal N falls in [lo, hi] (the 1-based
        ordinals of the group the daemon is about to run).  Fires BEFORE
        any verdict is derived: the claims stay leased, the lease expires
        or the pid reads dead, and a sibling's janitor requeues them —
        the verdict still publishes exactly once."""
        hi = lo if hi is None else hi
        for s in self.specs:
            if s.kind != "crash" or s.point != "daemon" or s.budget <= 0:
                continue
            if not self._instance_match(s):
                continue
            if not (lo <= s.arg <= hi):
                continue
            s.budget -= 1
            raise InjectedCrash(
                f"injected daemon crash on instance {s.instance} at job "
                f"ordinal {s.arg} (KSPEC_FAULT)"
            )

    def daemon_stalled(self) -> bool:
        """True once per `stall@daemon<i>` fault targeting this instance:
        the daemon then wedges (stops heartbeating, stops renewing
        leases, stops claiming) so the fleet supervisor's stall detector
        kills it and a sibling takes over its claims at lease expiry."""
        for s in self.specs:
            if s.kind != "stall" or s.point != "daemon" or s.budget <= 0:
                continue
            if not self._instance_match(s):
                continue
            s.budget -= 1
            return True
        return False

    # --- host-scoped faults (the routed fleet's chaos family) -----------
    def set_host(self, host: int) -> None:
        """Record which service host this process serves (the router's
        per-host service dirs launch their daemons with
        KSPEC_HOST_INSTANCE=i).  `kill@host<i>:N` / `partition@host<i>` /
        `skew@host<i>:SECS` then fire only in the targeted host's
        processes — one composed plan string drives a whole multi-host
        drill, each fault landing on exactly its target."""
        self.host = int(host)

    def _host_match(self, s: _Spec) -> bool:
        return (
            s.host is not None
            and self.host is not None
            and s.host == self.host
        )

    def host_kill(self, lo: int, hi: Optional[int] = None) -> None:
        """Raise InjectedCrash if a `kill@host<i>:N` fault targets this
        host and job ordinal N falls in [lo, hi] — the daemon-side hook,
        called next to `daemon_crash` before any verdict is derived.
        The router sees the host's heartbeats go stale and re-routes its
        pending jobs; leased claims come back through the takeover
        protocol, so the verdict still publishes exactly once."""
        hi = lo if hi is None else hi
        for s in self.specs:
            if s.kind != "kill" or s.budget <= 0:
                continue
            if not self._host_match(s):
                continue
            if not (lo <= s.arg <= hi):
                continue
            s.budget -= 1
            raise InjectedCrash(
                f"injected host kill on host {s.host} at job ordinal "
                f"{int(s.arg)} (KSPEC_FAULT)"
            )

    def host_partition(self) -> int:
        """Number of jobs host i must run cache-partitioned (once per
        `partition@host<i>[:N]` fault targeting this host, then 0).  The
        daemon consumes it at a claim sweep: for that many jobs every
        state-cache lookup degrades to a typed `cache-fallback` cold run
        and every publish is deferred, re-published on heal."""
        for s in self.specs:
            if s.kind != "partition" or s.budget <= 0:
                continue
            if not self._host_match(s):
                continue
            s.budget -= 1
            return int(s.arg)
        return 0

    def skew_s(self) -> float:
        """Injected wall-clock shift for this host's cross-host-visible
        timestamps (claim leases, heartbeat records); 0.0 without a
        matching `skew@host<i>:SECS`.  Not budget-consumed: a drifted
        clock drifts for the whole process lifetime."""
        total = 0.0
        for s in self.specs:
            if s.kind == "skew" and self._host_match(s):
                total += float(s.arg)
        return total

    def set_local_shards(self, shards) -> None:
        """Record which shards this process hosts (the sharded engine's
        mesh device->process map).  Shard-scoped faults then fire only on
        the targeted shard's host — the peers sail past the injection
        point and block in their next collective, which is precisely the
        one-process-died failure the fleet supervisor exists to catch."""
        self.local_shards = frozenset(int(s) for s in shards)

    def validate_shards(self, shard_count: int) -> None:
        """Reject shard scopes outside the mesh (same fail-loudly rule as
        the level >= 1 parse check: a typo'd `crash@shard5:...` on a
        2-shard run would otherwise silently rehearse nothing on EVERY
        process and report the drill as passed)."""
        for s in self.specs:
            if s.shard is not None and s.shard >= shard_count:
                raise ValueError(
                    f"fault plan {self.spec!r}: shard {s.shard} is out of "
                    f"range for a {shard_count}-shard mesh (valid: "
                    f"0..{shard_count - 1})"
                )

    def _is_local(self, s: _Spec) -> bool:
        return (
            s.shard is None
            or self.local_shards is None
            or s.shard in self.local_shards
        )

    def crash(self, point: str, depth: int, ckpt_depth=None) -> None:
        """Raise InjectedCrash if a crash fault matches this (point, depth).

        `ckpt_depth` (level boundaries only): the newest durably
        checkpointed level, or None when the run isn't checkpointing.
        With checkpointing, a level crash is DEFERRED until a checkpoint
        at or past the target level exists — otherwise checkpoint_every>1
        would resume below the target and re-fire forever (e.g. crash@
        level:7 with saves only at even levels).  The crash then fires at
        the first level boundary where resuming cannot re-trigger it, so
        a supervised restart always converges."""
        for s in self.specs:
            if s.kind != "crash" or s.point != point or s.budget <= 0:
                continue
            if not self._is_local(s):
                continue
            # merge ordinals are per-process counters, not BFS levels:
            # the resume-depth relief below does not apply
            if point != "merge" and self.start_depth >= s.arg:
                continue  # resumed at/past the target: counts as fired
            if point == "level":
                if depth < s.arg:
                    continue
                if ckpt_depth is not None and ckpt_depth < s.arg:
                    continue  # not durably past the target yet: defer
            elif depth != s.arg:
                continue
            s.budget -= 1
            raise InjectedCrash(
                f"injected crash at {point}:{depth}"
                + (f" on shard {s.shard}" if s.shard is not None else "")
                + " (KSPEC_FAULT)"
            )

    def enospc(self, point: str, n: int) -> None:
        """Raise an injected OSError(ENOSPC) if an `enospc@<point>:N`
        fault matches.  `n` is the BFS level for ckpt/plog (resume-depth
        relief applies, like crash@level) and a per-process ordinal for
        spill/merge/cache (in-process test use, like crash@merge).  Raised at
        each writer's pre-promote point, so the on-disk state it leaves
        is exactly what a real full disk leaves: old files intact, tmp
        cleaned up, every promoted generation verifiable."""
        for s in self.specs:
            if s.kind != "enospc" or s.point != point or s.budget <= 0:
                continue
            if not self._is_local(s):
                continue
            if point in ("ckpt", "plog") and self.start_depth >= s.arg:
                continue  # resumed at/past the target: counts as fired
            if n != s.arg:
                continue
            s.budget -= 1
            raise OSError(
                errno.ENOSPC,
                f"No space left on device (injected by KSPEC_FAULT "
                f"enospc@{point}:{n})",
            )

    def stalled(self, depth: int) -> bool:
        """True once per `stall@level:N` fault when level N is done: the
        resource governor's deadline watchdog then reports the level as
        stalled (resilience.resources).  Resume-depth relief applies, so
        a post-reclaim resume converges instead of stall-looping."""
        for s in self.specs:
            if s.kind != "stall" or s.budget <= 0 or not self._is_local(s):
                continue
            if s.point == "daemon":
                continue  # daemon wedges fire via daemon_stalled(), never
                # at an engine level boundary (their arg is no level)
            if self.start_depth >= s.arg:
                continue
            if depth >= s.arg:
                s.budget -= 1
                return True
        return False

    def chunk_error(self, escalated: bool) -> Optional[Exception]:
        """Error to inject into the next chunk/exchange step, or None.

        compile_oom fires only on escalated (per-action width tuple)
        attempts — matching the real failure mode it rehearses, and the
        only attempt shape for which the engines have a compile fallback.
        """
        for s in self.specs:
            if not self._is_local(s):
                continue
            if s.kind == "transient_device_err" and s.budget > 0:
                s.budget -= 1
                return RuntimeError(TRANSIENT_MARKER)
            if s.kind == "compile_oom" and s.budget > 0 and escalated:
                s.budget -= 1
                return RuntimeError(OOM_MARKER)
        return None

    def flip(self, site: str, n: int, ckpt_depth=None):
        """The matching `flip@<site>:N` spec (truthy; carries the shard
        scope so the sharded engine flips the TARGETED shard's buffer),
        once per spec, else None — the caller then performs the actual
        bit flip (or, for the exchange framing check, the
        corrupted-digest observation) at its site.

        Level-keyed sites (frontier/fpset/exchange/ckpt): `n` is a BFS
        level; resume-depth relief applies, and with `ckpt_depth` given
        (a checkpointing run's newest durable level) firing DEFERS until
        a generation at or past the target exists — the same convergence
        rule as FaultPlan.crash, so a supervised restart resumes at or
        past N and never flip-loops.  `spill`: `n` is a per-process
        ordinal (in-process test use, like crash@merge)."""
        for s in self.specs:
            if s.kind != "flip" or s.point != site or s.budget <= 0:
                continue
            if not self._is_local(s):
                continue
            if site in ("spill", "cache"):
                # per-process ordinals (in-process test use, like
                # crash@merge): cache = the Nth state-space-cache
                # artifact published by this process
                if n != s.arg:
                    continue
            else:
                if self.start_depth >= s.arg:
                    continue  # resumed at/past the target: counts as fired
                if n < s.arg:
                    continue
                if ckpt_depth is not None and ckpt_depth < s.arg:
                    continue  # not durably past the target yet: defer
            s.budget -= 1
            return s
        return None

    def should_corrupt(self, depth: int) -> bool:
        """True if the checkpoint just written at `depth` must be corrupted."""
        for s in self.specs:
            if s.kind == "corrupt_ckpt" and s.budget > 0 and self._is_local(s):
                if s.arg is None or s.arg == depth:
                    s.budget -= 1
                    return True
        return False


#: injected_skew_s cache: (KSPEC_FAULT, KSPEC_HOST_INSTANCE) -> seconds.
#: The lease-stamp path calls this on every renewal; re-parsing the plan
#: each time would put a parser on the queue hot path for nothing — the
#: env pair is fixed for a process's lifetime in production and varies
#: only across monkeypatched tests, which the keyed cache handles.
_SKEW_CACHE: dict = {}


def injected_skew_s() -> float:
    """Wall-clock shift (seconds) the `skew@host<i>:SECS` fault injects
    into timestamps THIS process writes into cross-host-visible metadata
    (claim leases — service/queue.py — and heartbeat records).  0.0
    unless KSPEC_FAULT carries a skew spec targeting this process's
    KSPEC_HOST_INSTANCE; never raises (an unparseable plan is the
    engine/CLI's error to report, not the lease writer's)."""
    key = (
        os.environ.get(ENV_VAR, ""),
        os.environ.get("KSPEC_HOST_INSTANCE", ""),
    )
    if key not in _SKEW_CACHE:
        skew = 0.0
        if key[0] and key[1]:
            try:
                plan = FaultPlan(key[0])
                plan.set_host(int(key[1]))
                skew = plan.skew_s()
            except (ValueError, TypeError):
                skew = 0.0
        _SKEW_CACHE[key] = skew
    return _SKEW_CACHE[key]


def corrupt_file(path: str, n_bytes: int = 64) -> None:
    """Flip a run of bytes in the middle of `path` (simulated bit rot).

    Lands inside an npz member's compressed/stored data, so both the zip
    CRC and the manifest checksums must catch it on the next load."""
    size = os.path.getsize(path)
    with open(path, "r+b") as fh:
        fh.seek(max(0, size // 2 - n_bytes // 2))
        chunk = fh.read(n_bytes)
        fh.seek(max(0, size // 2 - n_bytes // 2))
        fh.write(bytes(b ^ 0xFF for b in chunk))
