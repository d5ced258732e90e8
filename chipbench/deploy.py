"""Builds a configuration's deployment and fills its zones from the seed.

A configuration file (``configs/<name>.json``) names the entry point
(``csd``: one ``ZonedDevice`` behind ``NvmCsd``; ``scheduler``: a
``StripedZoneArray`` behind ``OffloadScheduler``), the member geometry and the
contents of each zone, whose ``dist`` names its generator,
``zones/<dist>.py``. Member latency emulation stays at the file's values,
which every configuration sets to zero. No tier or other tuning option is
passed: each cell measures the path the system chooses itself.
"""
from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

import named

__all__ = ["Deployment", "build", "zone_data", "seed_words"]

# elements per generator chunk: the chunking, and not the thread count,
# fixes which numbers land where, so any machine makes the same bytes
_CHUNK = 1 << 22


def seed_words(seed: int, *salt: int) -> np.random.SeedSequence:
    """A ``SeedSequence`` for ``seed`` (any whole number, large or
    negative) and a salt that separates the streams of one run."""
    return np.random.SeedSequence([seed % (1 << 64), *salt])


def zone_data(spec: dict, seed: int, n_bytes: int,
              block_bytes: int) -> np.ndarray:
    """The bytes of one zone of ``n_bytes`` capacity as the configuration's
    ``zones`` entry says, generated from ``seed`` on a few host threads into
    one buffer.

    The generator, ``zones/<dist>.py``, has ``fill(spec, g, out, start)``:
    it fills ``out``, the ``_CHUNK`` elements of the zone from element
    ``start``, from its own seeded ``g``. It may have
    ``elements(spec, capacity)``, the elements the zone holds where that is
    fewer than its capacity; the zone is then zero-padded to whole blocks."""
    gen = named.zone_kind(spec)
    dtype = np.dtype(spec["dtype"])
    cap = n_bytes // dtype.itemsize
    n = int(gen.elements(spec, cap)) if hasattr(gen, "elements") else cap
    if not 0 <= n <= cap:
        raise ValueError(f"zone {spec['zone']}: {spec['dist']} asks for "
                         f"{n} elements, the zone holds {cap}")
    per_block = block_bytes // dtype.itemsize
    out = np.empty(-(-n // per_block) * per_block, dtype)
    out[n:] = 0
    kids = seed_words(seed, 1, int(spec["zone"])).spawn(-(-n // _CHUNK))

    def fill(i: int) -> None:
        g = np.random.Generator(np.random.PCG64(kids[i]))
        start = i * _CHUNK
        gen.fill(spec, g, out[start:min(start + _CHUNK, n)], start)

    with ThreadPoolExecutor(min(12, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, range(len(kids))))
    return out


@dataclass
class Deployment:
    """What a run drives: the storage (``device``), the entry object
    (``csd`` or ``scheduler``) and the benchmark's own copy of every zone it
    filled, which the reference reads."""

    config: dict
    storage: object
    entry: object
    data: dict = field(default_factory=dict)   # zone -> np.ndarray
    seconds: dict = field(default_factory=dict)  # set-up phase -> seconds

    @property
    def block_bytes(self) -> int:
        return int(self.config["block_bytes"])

    def zone_blocks(self, zone: int) -> int:
        """Blocks the benchmark wrote into data zone ``zone``, from its own
        copy of the bytes, never from what the storage reports."""
        return self.data[zone].nbytes // self.block_bytes

    def extent_blocks(self, job) -> int:
        """Blocks of ``job``'s extent: the ones it names, or the rest of the
        zone from its start block."""
        if job.n_blocks is not None:
            return int(job.n_blocks)
        return self.zone_blocks(job.zone) - job.block_off

    def close(self) -> None:
        stop = getattr(self.entry, "stop", None)
        if stop is not None:
            stop()


def build(config: dict, seed: int) -> Deployment:
    """Make the configuration's members, fill every data zone through the
    system's own append path, and open its entry point."""
    from repro.zns import ZonedDevice

    def member(num_zones: int, zone_bytes: int) -> "ZonedDevice":
        return ZonedDevice(
            num_zones=num_zones, zone_bytes=zone_bytes,
            block_bytes=int(config["block_bytes"]),
            read_us_per_block=float(config["read_us_per_block"]),
            append_us_per_block=float(config["append_us_per_block"]))

    zone_bytes = int(config["zone_bytes"])
    members = int(config["members"])
    if config["entry"] == "csd":
        from repro.core import NvmCsd
        storage = member(int(config["num_zones"]), zone_bytes)
        entry = NvmCsd(storage)
    elif config["entry"] == "scheduler":
        from repro.array import OffloadScheduler, StripedZoneArray
        storage = StripedZoneArray(
            [member(int(config["num_zones"]), zone_bytes)
             for _ in range(members)],
            stripe_blocks=int(config["stripe_blocks"]),
            redundancy=config["redundancy"])
        entry = OffloadScheduler(storage)
    else:
        raise ValueError(f"unknown entry {config['entry']!r}")
    dep = Deployment(config, storage, entry)
    dep.seconds = {"generate": 0.0, "append": 0.0}
    logical = zone_bytes * (members if config["entry"] == "scheduler" else 1)
    for spec in config["zones"]:
        t0 = time.perf_counter()
        data = zone_data(spec, seed, logical, dep.block_bytes)
        if not data.size:      # left empty: the mix's appends fill it
            continue
        t1 = time.perf_counter()
        storage.zone_append(int(spec["zone"]), data)
        dep.seconds["generate"] += t1 - t0
        dep.seconds["append"] += time.perf_counter() - t1
        dep.data[int(spec["zone"])] = data
    return dep
