"""Persistent sessions + the tiered KV memory hierarchy (ISSUE 18).

A multi-turn SESSION is a first-class object here: a conversation's KV
survives stream close, reattaches on a later ``submit(session_id=...)``
— on any replica, via the router's ``FleetSessionIndex`` — and
persists across restarts. Three tiers:

  * **HBM (resident)** — the engine's paged pool, untouched: a
    finished session stream PARKS its blocks (ownership transferred
    off the slot, refcounts held) instead of freeing them, up to the
    engine's ``session_hbm_max``; reattach on the same replica is a
    radix re-seed, zero bytes moved.
  * **host-DRAM (warm)** — this module's ``SessionStore``: a bounded
    LRU of PR 11 ``KVBlockPayload``s (int8-aware, ``wire_version``-
    checked), demoted out of HBM by the engine, promoted back on
    resume.
  * **disk (cold)** — ``SessionStore`` spills LRU sessions past its
    DRAM budget to ``<dir>/<session_id>/`` with the CheckpointManager
    discipline (utils/manifest): data file first, per-file sha256
    manifest published atomically LAST, quarantine on mismatch — a
    torn or bit-flipped session can only MISS (the request re-prefills
    losslessly), never serve wrong KV.

Eviction demotes cold-but-live sessions down the hierarchy instead of
preempting (LRU, with per-tenant session caps riding the PR 15
``TenantConfig`` vocabulary); ``prefetch()`` promotes up
asynchronously ahead of a predicted resume. Every decline — version
mismatch, evicted, corrupt — is a counted, evented miss whose fallback
is the engine's ordinary (bitwise-lossless) re-prefill.

The store is HOST-ONLY: no jax, no device work, no compiled programs —
the zero-steady-state-recompile contract is held by construction.

Offline CLI for the disk tier (mirrors the checkpoint CLI)::

    python -m pytorchdistributed_tpu.serving.sessions ls <dir>
    python -m pytorchdistributed_tpu.serving.sessions verify <dir>
    python -m pytorchdistributed_tpu.serving.sessions gc <dir> \
        [--max-age SECONDS] [--keep-bytes BYTES] [--dry-run]
"""

from __future__ import annotations

import json
import pathlib
import re
import time

from pytorchdistributed_tpu.utils.manifest import (
    QUARANTINE_DIR,
    quarantine_dir,
    read_manifest,
    verify_dir_manifest,
    write_dir_manifest,
)

__all__ = [
    "SessionStore",
    "session_id_ok",
    "main",
]

PAYLOAD_NAME = "payload.json"

# session ids become directory names on the disk tier: a strict charset
# (no leading dot — no traversal, no hidden dirs) is the whole
# sanitization story
_SID_RE = re.compile(r"[A-Za-z0-9][A-Za-z0-9._:-]{0,127}$")


def session_id_ok(session_id) -> bool:
    return bool(isinstance(session_id, str)
                and _SID_RE.fullmatch(session_id))


def _check_sid(session_id: str) -> str:
    if not (isinstance(session_id, str)
            and _SID_RE.fullmatch(session_id)):
        raise ValueError(
            f"session_id must match {_SID_RE.pattern!r} (it names a "
            f"directory on the disk tier), got {session_id!r}")
    return session_id


class _Record:
    """One DRAM-tier entry."""

    __slots__ = ("payload", "tenant", "nbytes", "last_used", "on_disk")

    def __init__(self, payload, tenant: str, now: float,
                 on_disk: bool = False):
        self.payload = payload
        self.tenant = tenant
        self.nbytes = int(payload.nbytes)
        self.last_used = now
        # True while the disk copy is byte-identical to ``payload`` —
        # a demotion then skips the rewrite; any fresh put() clears it
        self.on_disk = on_disk


class SessionStore:
    """The host-DRAM + disk tiers of the session hierarchy.

    Args:
      directory: disk-tier root (None = DRAM-only; demotions past the
        DRAM budget are DROPPED and counted instead of spilled).
        Reopening a store over an existing directory rediscovers every
        published session — restart survival.
      dram_bytes: DRAM-tier budget over payload ``nbytes``; LRU
        sessions demote to disk (or drop) once it's exceeded.
      disk_bytes: optional disk-tier budget; oldest disk sessions are
        dropped once exceeded (the online twin of ``gc --keep-bytes``).
      tenants: optional ``{name: TenantConfig}`` — a tenant at its
        ``max_sessions`` cap evicts its OWN least-recent session
        (demoted down-tier, dropped off the bottom) before a new one
        is admitted; other tenants are never touched.
      wire_version: the KV payload schema this store will serve;
        stored sessions carrying any other version DECLINE at get()
        (counted, never served). Defaults to the engine's current
        ``KV_WIRE_VERSION``.
      clock: injectable time source for ages/GC (tests).
      faults: optional ``faults.FaultInjector`` consulted on every
        disk-tier touch (``on_io``); ``None`` falls back to the
        process-global ``PTD_FAULTS`` injector. An injected io_err on
        spill or load is absorbed here — counted as ``io_errors``, the
        session dropped or missed (re-prefill recovers it) — never a
        crash."""

    def __init__(self, directory: str | pathlib.Path | None = None, *,
                 dram_bytes: int = 256 << 20,
                 disk_bytes: int | None = None,
                 tenants: dict | None = None,
                 wire_version: int | None = None,
                 clock=None,
                 faults=None):
        if wire_version is None:
            from pytorchdistributed_tpu.serving.engine import (
                KV_WIRE_VERSION,
            )

            wire_version = KV_WIRE_VERSION
        self.directory = (pathlib.Path(directory)
                          if directory is not None else None)
        self.dram_bytes = int(dram_bytes)
        self.disk_bytes = disk_bytes
        self.wire_version = int(wire_version)
        self._tenants = dict(tenants or {})
        self._faults = faults
        self._clock = clock or time.time
        self._dram: dict[str, _Record] = {}  # insertion order == LRU
        #: sid -> {"nbytes", "tenant", "time"} for every PUBLISHED disk
        #: session (manifest present) — rebuilt by scanning on open
        self._disk: dict[str, dict] = {}
        self._prefetch: dict[str, object] = {}
        self._pool = None  # lazy ThreadPoolExecutor for prefetch()
        self.reset_stats()
        if self.directory is not None:
            self.directory.mkdir(parents=True, exist_ok=True)
            self._scan_disk()

    # -- stats ---------------------------------------------------------

    def reset_stats(self) -> None:
        self._stats = dict(puts=0, hits_hbm=0, hits_dram=0, hits_disk=0,
                           misses=0, promotes=0, demotes=0,
                           spilled_bytes=0, dropped=0, tenant_evicted=0,
                           quarantined=0, version_declines=0, torn=0,
                           prefetches=0, io_errors=0)

    def _io_hook(self, what: str) -> None:
        """Consult the fault injector before a disk-tier touch.

        slow_io sleeps here (latency, not failure); io_err raises
        OSError, which the spill/load call sites absorb."""
        inj = self._faults
        if inj is None:
            from pytorchdistributed_tpu.faults import inject as _inject

            inj = _inject.active()
        if inj is not None:
            inj.on_io(what)

    def stats(self) -> dict:
        out = dict(self._stats)
        out["dram_sessions"] = len(self._dram)
        out["dram_bytes"] = sum(r.nbytes for r in self._dram.values())
        out["disk_sessions"] = len(self._disk)
        out["disk_bytes"] = sum(m["nbytes"] for m in self._disk.values())
        return out

    # -- the tiers -----------------------------------------------------

    def __contains__(self, session_id: str) -> bool:
        return self.peek_tier(session_id) is not None

    def peek_tier(self, session_id: str) -> str | None:
        """"dram" | "disk" | None — no promotion, no LRU touch."""
        if session_id in self._dram:
            return "dram"
        if session_id in self._disk or session_id in self._prefetch:
            return "disk"
        return None

    def put(self, session_id: str, payload, *,
            tenant: str = "default") -> None:
        """Admit (or refresh) a session into the DRAM tier, then
        rebalance: per-tenant cap first, DRAM budget next (LRU demotes
        to disk / drops), disk budget last."""
        _check_sid(session_id)
        self._drop_prefetch(session_id)
        now = float(self._clock())
        self._dram.pop(session_id, None)
        self._dram[session_id] = _Record(payload, tenant, now)
        # a refreshed session's disk copy (if any) is stale now
        if self._disk.pop(session_id, None) is not None:
            self._remove_disk_dir(session_id)
        self._stats["puts"] += 1
        self._enforce_tenant_cap(tenant)
        self._enforce_dram()
        self._enforce_disk()

    def get(self, session_id: str):
        """``(payload, tier)`` — "dram" or "disk" — or ``None`` on any
        miss/decline. A disk hit verifies the manifest BEFORE parsing
        (corruption quarantines, a missing manifest is a torn write:
        both are misses, never wrong KV) and promotes to DRAM."""
        rec = self._dram.get(session_id)
        if rec is not None:
            # LRU touch = move to the tail
            del self._dram[session_id]
            self._dram[session_id] = rec
            rec.last_used = float(self._clock())
            self._stats["hits_dram"] += 1
            return rec.payload, "dram"
        loaded = self._take_prefetch(session_id)
        if loaded is None:
            loaded = self._load_disk(session_id)
        if loaded is None:
            self._stats["misses"] += 1
            return None
        payload, tenant = loaded
        now = float(self._clock())
        self._dram[session_id] = _Record(payload, tenant, now,
                                         on_disk=True)
        self._stats["hits_disk"] += 1
        self._stats["promotes"] += 1
        self._enforce_dram()
        return payload, "disk"

    def prefetch(self, session_id: str) -> bool:
        """Start promoting a disk session to DRAM on a background
        thread (predicted resume); ``get()`` joins the in-flight read.
        Returns whether a prefetch was started."""
        if (session_id in self._dram or session_id in self._prefetch
                or session_id not in self._disk):
            return False
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="session-prefetch")
        self._prefetch[session_id] = self._pool.submit(
            self._load_disk, session_id)
        self._stats["prefetches"] += 1
        return True

    def drop(self, session_id: str) -> bool:
        """Forget a session everywhere (client delete)."""
        self._drop_prefetch(session_id)
        hit = self._dram.pop(session_id, None) is not None
        if session_id in self._disk:
            del self._disk[session_id]
            self._remove_disk_dir(session_id)
            hit = True
        return hit

    def flush(self) -> int:
        """Write every DRAM session without a current disk copy to the
        disk tier (shutdown path — restart survival for warm sessions).
        Returns how many landed; 0 with no directory."""
        if self.directory is None:
            return 0
        n = 0
        for sid, rec in list(self._dram.items()):
            if not rec.on_disk and self._write_disk(sid, rec):
                n += 1
        self._enforce_disk()
        return n

    def close(self) -> None:
        self.flush()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    # -- eviction / budgets --------------------------------------------

    def _tenant_count(self, tenant: str) -> int:
        return (sum(1 for r in self._dram.values() if r.tenant == tenant)
                + sum(1 for m in self._disk.values()
                      if m.get("tenant") == tenant))

    def _tenant_cap(self, tenant: str) -> int | None:
        cfg = self._tenants.get(tenant)
        return getattr(cfg, "max_sessions", None) if cfg else None

    def _enforce_tenant_cap(self, tenant: str) -> None:
        cap = self._tenant_cap(tenant)
        if cap is None:
            return
        while self._tenant_count(tenant) > cap:
            # coldest first: oldest disk session, else LRU DRAM one
            victim = next((sid for sid, m in self._disk.items()
                           if m.get("tenant") == tenant), None)
            if victim is not None:
                del self._disk[victim]
                self._remove_disk_dir(victim)
            else:
                victim = next(sid for sid, r in self._dram.items()
                              if r.tenant == tenant)
                del self._dram[victim]
            self._stats["tenant_evicted"] += 1

    def _enforce_dram(self) -> None:
        used = sum(r.nbytes for r in self._dram.values())
        while used > self.dram_bytes and len(self._dram) > 1:
            sid, rec = next(iter(self._dram.items()))  # LRU head
            del self._dram[sid]
            used -= rec.nbytes
            if self.directory is not None:
                landed = rec.on_disk
                if not landed and self._write_disk(sid, rec):
                    landed = True
                    self._stats["spilled_bytes"] += rec.nbytes
                if landed:
                    self._stats["demotes"] += 1
                else:
                    # spill failed (io_err / disk full): the session is
                    # gone from every tier — a counted drop the client
                    # recovers from by re-prefilling, never a crash
                    self._stats["dropped"] += 1
            else:
                self._stats["dropped"] += 1
        self._enforce_disk()

    def _enforce_disk(self) -> None:
        if self.disk_bytes is None:
            return
        used = sum(m["nbytes"] for m in self._disk.values())
        while used > self.disk_bytes and self._disk:
            sid = min(self._disk, key=lambda s: self._disk[s]["time"])
            used -= self._disk[sid]["nbytes"]
            del self._disk[sid]
            self._remove_disk_dir(sid)
            self._stats["dropped"] += 1

    # -- disk tier -----------------------------------------------------

    def _session_dir(self, session_id: str) -> pathlib.Path:
        return self.directory / session_id

    def _scan_disk(self) -> None:
        """Rediscover published sessions after a restart. Directories
        without a manifest are torn writes — invisible (counted once
        here), reaped by gc; never an error, never served."""
        for entry in sorted(self.directory.iterdir()):
            if not entry.is_dir() or entry.name == QUARANTINE_DIR:
                continue
            man = read_manifest(entry)
            if man is None:
                self._stats["torn"] += 1
                continue
            self._disk[entry.name] = dict(
                nbytes=int(man.get("nbytes", sum(
                    f["size"] for f in man.get("files", {}).values()))),
                tenant=str(man.get("tenant", "default")),
                time=float(man.get("time", 0.0)),
                wire_version=int(man.get("wire_version", 1)))

    def _write_disk(self, session_id: str, rec: _Record) -> bool:
        """Spill one DRAM session to disk; False on I/O failure. A
        failed spill never publishes (the manifest is the last write),
        so readers see a torn dir at worst — a miss, never wrong KV."""
        from pytorchdistributed_tpu.serving.engine import (
            kv_payload_to_wire,
        )

        sdir = self._session_dir(session_id)
        try:
            self._io_hook("session_spill")
            sdir.mkdir(parents=True, exist_ok=True)
            path = sdir / PAYLOAD_NAME
            tmp = path.with_name(path.name + ".tmp")
            tmp.write_text(json.dumps(kv_payload_to_wire(rec.payload)))
            import os

            os.replace(tmp, path)
            # the manifest IS the publish: until it lands, the session
            # is torn-by-definition and every reader treats it as a miss
            write_dir_manifest(sdir, extra=dict(
                session=session_id, tenant=rec.tenant, nbytes=rec.nbytes,
                wire_version=int(rec.payload.wire_version)))
        except OSError:
            self._stats["io_errors"] += 1
            return False
        rec.on_disk = True
        self._disk[session_id] = dict(
            nbytes=rec.nbytes, tenant=rec.tenant,
            time=float(self._clock()),
            wire_version=int(rec.payload.wire_version))
        return True

    def _load_disk(self, session_id: str):
        """Verify + parse one disk session; None on every decline
        (missing, torn, corrupt→quarantine, version mismatch)."""
        if self.directory is None:
            return None
        try:
            self._io_hook("session_load")
        except OSError:
            # transient read failure, NOT corruption evidence: count it
            # and miss (caller re-prefills); the disk copy stays put
            self._stats["io_errors"] += 1
            return None
        sdir = self._session_dir(session_id)
        if not sdir.is_dir():
            self._disk.pop(session_id, None)
            return None
        ok, verified, detail = verify_dir_manifest(sdir)
        if not verified:
            self._stats["torn"] += 1
            self._disk.pop(session_id, None)
            return None
        if not ok:
            # positive evidence of corruption: move it aside as
            # post-mortem evidence — this sid can now only MISS
            quarantine_dir(sdir, root=self.directory)
            self._disk.pop(session_id, None)
            self._stats["quarantined"] += 1
            return None
        from pytorchdistributed_tpu.serving.engine import (
            kv_payload_from_wire,
        )

        try:
            wire = json.loads((sdir / PAYLOAD_NAME).read_text())
            payload = kv_payload_from_wire(wire)
        except (OSError, ValueError, KeyError, TypeError):
            quarantine_dir(sdir, root=self.directory)
            self._disk.pop(session_id, None)
            self._stats["quarantined"] += 1
            return None
        if payload.wire_version != self.wire_version:
            # not corrupt — a schema from another era. Decline loudly;
            # gc reaps it by age
            self._stats["version_declines"] += 1
            return None
        meta = self._disk.get(session_id) or {}
        return payload, str(meta.get("tenant", "default"))

    def _remove_disk_dir(self, session_id: str) -> None:
        if self.directory is None:
            return
        sdir = self._session_dir(session_id)
        if sdir.exists():
            import shutil

            shutil.rmtree(sdir, ignore_errors=True)

    def _take_prefetch(self, session_id: str):
        fut = self._prefetch.pop(session_id, None)
        return None if fut is None else fut.result()

    def _drop_prefetch(self, session_id: str) -> None:
        fut = self._prefetch.pop(session_id, None)
        if fut is not None:
            try:
                fut.result()
            except Exception:
                pass

    # -- offline inventory (the CLI's engine) --------------------------

    def ls(self) -> list[dict]:
        now = float(self._clock())
        rows = []
        for sid, rec in self._dram.items():
            rows.append(dict(session=sid, tier="dram", tenant=rec.tenant,
                             nbytes=rec.nbytes,
                             age_s=round(now - rec.last_used, 1)))
        for sid, m in self._disk.items():
            if sid in self._dram:
                continue
            rows.append(dict(session=sid, tier="disk",
                             tenant=m.get("tenant", "default"),
                             nbytes=m["nbytes"],
                             age_s=round(now - m.get("time", now), 1)))
        return rows

    def verify(self) -> list[tuple[str, bool, bool, str]]:
        """Manifest-check every disk session (no payload parsing, no
        device work): ``(sid, ok, verified, detail)`` per directory."""
        if self.directory is None:
            return []
        out = []
        for entry in sorted(self.directory.iterdir()):
            if not entry.is_dir() or entry.name == QUARANTINE_DIR:
                continue
            ok, verified, detail = verify_dir_manifest(entry)
            out.append((entry.name, ok, verified, detail))
        return out

    def gc(self, *, max_age_s: float | None = None,
           keep_bytes: int | None = None,
           dry_run: bool = False) -> dict:
        """Reap the disk tier: torn directories always; published
        sessions older than ``max_age_s``; then oldest-first until the
        tier fits ``keep_bytes``. Never touches quarantine/ (evidence)
        or the DRAM tier."""
        if self.directory is None:
            return dict(removed=0, kept=0, bytes_kept=0)
        now = float(self._clock())
        removed = 0
        for entry in sorted(self.directory.iterdir()):
            if not entry.is_dir() or entry.name == QUARANTINE_DIR:
                continue
            sid = entry.name
            man = read_manifest(entry)
            stale = man is None  # torn write: always reap
            if (not stale and max_age_s is not None
                    and now - float(man.get("time", 0.0)) > max_age_s):
                stale = True
            if stale:
                removed += 1
                if not dry_run:
                    self._disk.pop(sid, None)
                    self._remove_disk_dir(sid)
        if keep_bytes is not None:
            order = sorted(self._disk, key=lambda s: self._disk[s]["time"])
            used = sum(self._disk[s]["nbytes"] for s in order)
            for sid in order:
                if used <= keep_bytes:
                    break
                used -= self._disk[sid]["nbytes"]
                removed += 1
                if not dry_run:
                    del self._disk[sid]
                    self._remove_disk_dir(sid)
        return dict(removed=removed, kept=len(self._disk),
                    bytes_kept=sum(m["nbytes"]
                                   for m in self._disk.values()))


def main(argv=None) -> int:
    """Offline disk-tier CLI (see module docstring). ``verify`` exits
    1 when any published session is corrupt (torn/unverified ones
    report but do not fail — they can only miss)."""
    import argparse

    parser = argparse.ArgumentParser(
        "pytorchdistributed_tpu.serving.sessions")
    sub = parser.add_subparsers(dest="cmd", required=True)
    ls = sub.add_parser("ls", help="list stored sessions")
    ls.add_argument("directory")
    ver = sub.add_parser("verify",
                         help="check every session's integrity manifest")
    ver.add_argument("directory")
    ver.add_argument("--strict", action="store_true",
                     help="also fail on torn sessions (no manifest)")
    gc = sub.add_parser("gc", help="reap torn/old/over-budget sessions")
    gc.add_argument("directory")
    gc.add_argument("--max-age", type=float, default=None,
                    metavar="SECONDS",
                    help="drop sessions older than this")
    gc.add_argument("--keep-bytes", type=int, default=None,
                    metavar="BYTES",
                    help="drop oldest sessions until the tier fits")
    gc.add_argument("--dry-run", action="store_true")
    args = parser.parse_args(argv)

    store = SessionStore(args.directory, dram_bytes=0)
    if args.cmd == "ls":
        rows = store.ls()
        for r in sorted(rows, key=lambda r: r["session"]):
            print(f"{r['session']:<32}  {r['tier']:<4}  "
                  f"{r['tenant']:<12}  {r['nbytes']:>12}  "
                  f"age {r['age_s']:.0f}s")
        total = sum(r["nbytes"] for r in rows)
        print(f"{len(rows)} session(s), {total} bytes")
        return 0
    if args.cmd == "verify":
        verdicts = store.verify()
        if not verdicts:
            print(f"no sessions under {args.directory}")
            return 1
        bad = 0
        for sid, ok, verified, detail in verdicts:
            status = ("OK" if ok and verified
                      else "TORN" if ok else "CORRUPT")
            if not ok or (args.strict and not verified):
                bad += 1
            print(f"{sid:<32}  {status:<8}  {detail}")
        print(f"{len(verdicts)} session(s), {bad} bad")
        return 1 if bad else 0
    out = store.gc(max_age_s=args.max_age, keep_bytes=args.keep_bytes,
                   dry_run=args.dry_run)
    tag = " (dry run)" if args.dry_run else ""
    print(f"removed {out['removed']} session(s){tag}, "
          f"{out['kept']} kept, {out['bytes_kept']} bytes")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
