"""The one traffic generator: reads a mix file and drives a deployment.

A mix (``traffic/<name>.json``) is data only::

    {"programs": {"<name>": {"kind": "<kind>", ...}, ...},  # programs/<kind>.py
     "tenants": [
        {"name": "default", "clients": 4,          # closed-loop clients
         "deck": [<job>, ...]                      # in order, round on round
         "draw": [[<weight>, <job>], ...]}]        # or shares, seeded order
     "limits": {"<compared number>": <limit>, ...}}

A job is an offload, ``{"offload": "<program>", "zone": 0}`` over the whole
zone or, with ``"blocks": [lo, hi]`` and ``"start": {"zipf": 0.99}``, over
``lo``..``hi`` blocks (uniform) from a scrambled-Zipfian start block (YCSB's
key chooser); or an append, ``{"append": 1, "blocks": 1}``, of seeded bytes.

Every stream is seeded from ``--seed``, so one seed gives the same
requests; a seed changes which requests, never how much work they hold.
A closed-loop client sends its next request when the previous one is
answered.
"""
from __future__ import annotations

import threading
import time
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Optional

import numpy as np

import named
from deploy import seed_words

__all__ = ["Job", "Record", "JobStream", "Driver", "program", "run_window",
           "warm_jobs", "scrambled_zipf"]

# YCSB's ScrambledZipfianGenerator draws a Zipfian rank over 10**10 items
# (zeta precomputed for theta 0.99) and hashes it onto the key space
_YCSB_ITEMS = 10_000_000_000
_YCSB_ZETAN = 26.46902820178302
_FNV_BASIS = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(1099511628211)
_BATCH = 4096   # jobs per refill of a deck stream
_DRAW = 200     # jobs per refill of a draw stream: each job's share of it


def scrambled_zipf(rng: np.random.Generator, n: int, size: int,
                   theta: float = 0.99) -> np.ndarray:
    """``size`` keys in ``[0, n)``, scrambled-Zipfian as YCSB draws them
    (Gray et al.'s Zipfian over 10**10 ranks, FNV-1a 64 of the rank,
    modulo ``n``)."""
    if theta != 0.99:
        raise ValueError("the precomputed zeta is for theta 0.99 only")
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1 - (2.0 / _YCSB_ITEMS) ** (1 - theta)) / (1 - zeta2 / _YCSB_ZETAN)
    u = rng.random(size)
    uz = u * _YCSB_ZETAN
    rank = np.floor(_YCSB_ITEMS * (eta * u - eta + 1) ** alpha)
    rank = np.where(uz < 1.0, 0.0, np.where(uz < zeta2, 1.0, rank))
    val = rank.astype(np.uint64)
    h = np.full(size, _FNV_BASIS, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= val & np.uint64(0xFF)
            h *= _FNV_PRIME
            val >>= np.uint64(8)
    signed = h.view(np.int64)
    mag = np.where(signed == np.iinfo(np.int64).min, 0, np.abs(signed))
    return (mag % n).astype(np.int64)


@dataclass(frozen=True)
class Job:
    kind: str                      # "offload" | "append"
    zone: int
    block_off: int = 0
    n_blocks: Optional[int] = None  # None: the whole zone
    program: str = ""
    payload: Optional[bytes] = None


@dataclass
class Record:
    """One request of the window, as the client saw it."""

    tenant: str
    job: Job
    t_done: float = 0.0
    value: object = None
    error: Optional[BaseException] = None
    cache_misses: int = 0
    n_blocks: int = 0              # the job's extent, by the benchmark's count
    reported_bytes: Optional[int] = None   # the bytes the program says it read

    @property
    def ok(self) -> bool:
        return self.error is None and self.t_done > 0


class JobStream:
    """An endless seeded sequence of one tenant's (or client's) jobs. A
    ``deck`` is sent in its listed order, round after round (a ranged job
    in it draws its length). A ``draw``
    is sent in rounds of ``_DRAW`` jobs that hold each job its weight's
    share, with the lengths of a ranged job spread evenly over its range,
    in an order drawn from the seed. So every seed sends the same work:
    only the order, the start blocks and the bytes differ. The first round
    is made here, before any window opens, and clients may share a
    stream."""

    def __init__(self, tenant: dict, seed_seq: np.random.SeedSequence,
                 zone_blocks: dict, block_bytes: int):
        self.spec = tenant
        self.rng = np.random.Generator(np.random.PCG64(seed_seq))
        self.zone_blocks = zone_blocks
        self.block_bytes = block_bytes
        self._lock = threading.Lock()
        self._buf = self._refill()[::-1]

    def __next__(self) -> Job:
        with self._lock:
            if not self._buf:
                self._buf = self._refill()[::-1]
            return self._buf.pop()

    def __iter__(self):
        return self

    def _refill(self) -> list[Job]:
        spec = self.spec
        if "deck" in spec:
            deck = spec["deck"]
            specs = deck * -(-_BATCH // len(deck))
            u_len = self.rng.random(len(specs))
        else:
            weights = np.array([w for w, _ in spec["draw"]], float)
            counts = np.round(weights / weights.sum() * _DRAW).astype(int)
            specs, u_len = [], []
            for (_, job), m in zip(spec["draw"], counts):
                specs += [job] * m
                u_len += list((np.arange(m) + 0.5) / m)
            order = self.rng.permutation(len(specs))
            specs = [specs[i] for i in order]
            u_len = np.asarray(u_len)[order]
        return self._make(specs, u_len)

    def _make(self, specs: list[dict], u_len: np.ndarray) -> list[Job]:
        n = len(specs)
        keys = None
        jobs = []
        for i, s in enumerate(specs):
            if "append" in s:
                nb = int(s["blocks"])
                jobs.append(Job("append", int(s["append"]), n_blocks=nb,
                                payload=self.rng.bytes(nb * self.block_bytes)))
                continue
            zone = int(s["zone"])
            if "blocks" not in s:
                jobs.append(Job("offload", zone, program=s["offload"]))
                continue
            lo, hi = s["blocks"]
            nb = lo + int(u_len[i] * (hi - lo + 1))
            starts = self.zone_blocks[zone] - hi + 1
            if keys is None:
                keys = scrambled_zipf(self.rng, starts, n,
                                      s["start"]["zipf"])
            jobs.append(Job("offload", zone, int(keys[i]), nb,
                            program=s["offload"]))
        return jobs


def program(spec: dict):
    """The system's ``Program`` for a mix's program spec, as its kind
    (``programs/<kind>.py``) builds it."""
    return named.program_kind(spec).build(spec)


class Driver:
    """Sends one job through the deployment's entry and fills its record."""

    def __init__(self, dep, programs: dict, annotate):
        self.dep = dep
        self.programs = programs
        self.ann = annotate
        self.csd = dep.config["entry"] == "csd"

    def send(self, rec: Record):
        """Submit ``rec.job``; returns a handle for :meth:`finish`."""
        job, ent = rec.job, self.dep.entry
        if self.csd:
            return None
        if job.kind == "append":
            with self.ann("bench.append"):
                return ent.submit_io("append", job.zone,
                                     data=np.frombuffer(job.payload, np.uint8),
                                     tenant=rec.tenant)
        with self.ann("bench.submit"):
            return ent.submit(self.programs[job.program], job.zone,
                              tenant=rec.tenant, block_off=job.block_off,
                              n_blocks=job.n_blocks)

    def finish(self, rec: Record, handle) -> None:
        job, ent = rec.job, self.dep.entry
        stats = None
        if self.csd:
            with self.ann("bench.bpf_run"):
                stats = ent.nvm_cmd_bpf_run(self.programs[job.program],
                                            job.zone, block_off=job.block_off,
                                            n_blocks=job.n_blocks)
            with self.ann("bench.bpf_result"):
                rec.value = ent.nvm_cmd_bpf_result()
        else:
            with self.ann("bench.wait"):
                comp = ent.wait(handle)
            rec.error = comp.error
            rec.value = comp.value
            stats = comp.stats
        if stats is not None:
            rec.cache_misses = stats.cache_misses
        if job.kind == "offload":
            rec.n_blocks = self.dep.extent_blocks(job)
            if stats is not None:
                rec.reported_bytes = stats.bytes_read
        else:
            rec.n_blocks = job.n_blocks
        rec.t_done = time.perf_counter()


def _closed_client(drv: Driver, tenant: str, stream: JobStream,
                   end: float, out: list) -> None:
    while time.perf_counter() < end:
        rec = Record(tenant, next(stream))
        out.append(rec)
        try:
            drv.finish(rec, drv.send(rec))
        except Exception as e:   # refused or failed: counted, never dropped
            rec.error = e


def streams(mix: dict, seed: int, dep) -> list[tuple[dict, list[JobStream]]]:
    """Each tenant with its streams: one per client of a ``draw`` tenant;
    one, shared by all its clients, for a ``deck`` tenant."""
    zb = {z: dep.zone_blocks(z) for z in dep.data}
    out = []
    for ti, t in enumerate(mix["tenants"]):
        n = int(t.get("clients", 1))
        if "deck" in t:
            one = JobStream(t, seed_words(seed, 2, ti, 0), zb,
                            dep.block_bytes)
            out.append((t, [one] * n))
        else:
            out.append((t, [JobStream(t, seed_words(seed, 2, ti, c), zb,
                                      dep.block_bytes) for c in range(n)]))
    return out


def warm_jobs(mix: dict, dep) -> list[Job]:
    """One job of every shape the mix can send: each whole-zone offload, and
    for short offloads each program at every chunk length a short extent
    can be cut into (1 .. stripe unit blocks, or 1 .. the longest extent on
    one device)."""
    jobs, seen = [], set()
    chunk = int(dep.config.get("stripe_blocks", 0)) or None
    for t in mix["tenants"]:
        specs = t.get("deck") or [s for _, s in t["draw"]]
        for s in specs:
            if "append" in s:
                key = ("append", s["append"], s["blocks"])
                if key not in seen:
                    seen.add(key)
                    jobs.append(Job("append", int(s["append"]),
                                    n_blocks=int(s["blocks"]),
                                    payload=bytes(int(s["blocks"])
                                                  * dep.block_bytes)))
                continue
            zone = int(s["zone"])
            if "blocks" not in s:
                key = (s["offload"], zone)
                if key not in seen:
                    seen.add(key)
                    jobs.append(Job("offload", zone, program=s["offload"]))
                continue
            top = min(chunk or s["blocks"][1], s["blocks"][1])
            for nb in range(1, top + 1):
                key = (s["offload"], zone, nb)
                if key not in seen:
                    seen.add(key)
                    jobs.append(Job("offload", zone, 0, nb,
                                    program=s["offload"]))
    return jobs


def run_window(dep, mix: dict, programs: dict, seed: int, seconds: float,
               annotate=None) -> tuple[list[Record], float]:
    """Run the mix's closed-loop clients for ``seconds``, let what is in
    flight finish, and return every record and the instant the window
    opened."""
    ann = annotate or (lambda name: nullcontext())
    drv = Driver(dep, programs, ann)
    tenant_streams = streams(mix, seed, dep)
    out: list[Record] = []
    t_open = time.perf_counter()
    end = t_open + seconds
    threads = [threading.Thread(target=_closed_client,
                                args=(drv, t["name"], s, end, out),
                                name=f"bench-{t['name']}-{c}")
               for t, ss in tenant_streams for c, s in enumerate(ss)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return out, t_open
