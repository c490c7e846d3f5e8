"""vstart: boot a REAL multi-process cluster.

This is the role of the reference's ``src/vstart.sh`` (1357 lines of shell
whose only job is to start N ceph-mon + M ceph-osd + mds/rgw as separate OS
processes on one machine) together with the daemon ``main()``s it execs
(``src/ceph_osd.cc:106``, ``src/ceph_mon.cc``).  Every daemon here is a real
``fork+exec``'d Python interpreter running exactly one Monitor / OSDService /
MDS / RGW on its own event loop; they find each other over the TCP messenger
through a shared **cluster spec** file — the monmap + config the reference
distributes via ``ceph.conf`` + the monmap file.

Layout of a run directory (``--run-dir``):

    cluster_spec.json      monmap + n_osds + config overrides
    mon.0.kv / osd.3.kv    per-daemon FileDB stores (WAL, crash-safe)
    osd.3.kv/block         raw block file when osd_objectstore=blockstore
    mon.0.log / osd.3.log  daemon stdout+stderr

The spec is deterministic: every mon builds the identical initial OSDMap
from it (the reference's ``monmaptool --create`` + ``osdmaptool
--createsimple`` seed), so independently-booted mons agree on epoch 1
without talking.

Why this exists: through round 4 every "live" test hosted all daemons in ONE
interpreter on one loop — fine for correctness, but a single GIL serialised
the whole data path (~27 MB/s).  Real processes give each OSD its own
interpreter, so daemon-path throughput can scale with the process count;
``tools/daemon_bench.py --multiprocess`` measures exactly that and
``tests/test_multiprocess.py`` proves kill/revive correctness across real
PIDs (SIGKILL, not cooperative ``stop()``).
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field


# ---------------------------------------------------------------------------
# Cluster spec


@dataclass
class ClusterSpec:
    """Everything a daemon needs to boot: the monmap + deterministic seed.

    The reference splits this across ceph.conf, the monmap file, and the
    mon store's initial osdmap; one JSON file carries all three here.
    """

    mon_addrs: list  # [[host, port], ...] — rank r binds mon_addrs[r]
    n_osds: int
    run_dir: str
    config: dict = field(default_factory=dict)
    keyring: dict = field(default_factory=dict)  # entity -> hex secret
    #: launcher-only knobs outside the typed Config schema (pool ids
    #: for mds/rgw daemons, rgw user database, ...)
    extras: dict = field(default_factory=dict)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(
                {
                    "mon_addrs": [list(a) for a in self.mon_addrs],
                    "n_osds": self.n_osds,
                    "run_dir": self.run_dir,
                    "config": self.config,
                    "keyring": self.keyring,
                    "extras": self.extras,
                },
                f,
                indent=1,
            )

    @classmethod
    def load(cls, path: str) -> "ClusterSpec":
        with open(path) as f:
            d = json.load(f)
        return cls(
            mon_addrs=[tuple(a) for a in d["mon_addrs"]],
            n_osds=d["n_osds"],
            run_dir=d["run_dir"],
            config=d.get("config", {}),
            keyring=d.get("keyring", {}),
            extras=d.get("extras", {}),
        )

    # -- deterministic seeds --------------------------------------------------

    def monmap(self):
        from ceph_tpu.mon import MonMap

        # deterministic uds:// endpoints derived from run_dir: every
        # daemon and client rebuilds the same monmap from the spec, so
        # co-located peers can dial the mon's Unix socket directly. The
        # messenger falls back to TCP whenever the socket is absent (a
        # remote run_dir) or the path exceeds the AF_UNIX limit.
        local = [
            f"uds://{os.path.join(self.run_dir, f'mon.{r}.sock')}"
            for r in range(len(self.mon_addrs))
        ]
        return MonMap(
            addrs=[tuple(a) for a in self.mon_addrs],
            local_addrs=local,
        )

    def build_config(self):
        from ceph_tpu.common.config import Config

        cfg = Config()
        for k, v in self.config.items():
            cfg.set(k, v)
        return cfg

    def initial_osdmap(self):
        return initial_osdmap(self.n_osds)

    def bytes_keyring(self) -> dict | None:
        if not self.keyring:
            return None
        return {k: bytes.fromhex(v) for k, v in self.keyring.items()}


def initial_osdmap(n_osds: int):
    """THE deterministic epoch-1 seed: one host per OSD (failures cross
    failure domains), straw2 root, rule 0 = indep (EC), rule 1 = firstn
    (replicated). Every mon of a cluster must build this identically from
    the spec alone, and the in-process live tier + daemon bench import it
    too, so single-process and multi-process behavior stay comparable."""
    from ceph_tpu.crush import builder as cb
    from ceph_tpu.crush.types import BucketAlg, CrushMap, Tunables
    from ceph_tpu.osd import OSDMap

    cmap = CrushMap(tunables=Tunables.jewel())
    host_ids, host_ws = [], []
    for h in range(n_osds):
        b = cb.make_bucket(
            cmap, -(h + 2), BucketAlg.STRAW2, 1, [h], [0x10000]
        )
        host_ids.append(b.id)
        host_ws.append(b.weight)
    cb.make_bucket(cmap, -1, BucketAlg.STRAW2, 10, host_ids, host_ws)
    cb.make_simple_rule(cmap, 0, -1, 1, "indep", 0)
    cb.make_simple_rule(cmap, 1, -1, 1, "firstn", 0)
    return OSDMap(crush=cmap, max_osd=n_osds)


def pick_ports(n: int) -> list[int]:
    """Reserve n distinct kernel-assigned loopback ports.

    All sockets stay open until every port is collected so the kernel can't
    hand the same port out twice; the (tiny, loopback-only) close->bind race
    is accepted, as vstart.sh accepts it with its fixed port ranges.
    """
    socks, ports = [], []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            ports.append(s.getsockname()[1])
            socks.append(s)
    finally:
        for s in socks:
            s.close()
    return ports


# ---------------------------------------------------------------------------
# Daemon mains (exec'd via python -m ceph_tpu.mon / ceph_tpu.osd / ...)


#: in-flight SIGTERM stop tasks: referenced here so the interpreter can
#: never garbage-collect one mid-stop (cephlint task-leak rule)
_TERM_TASKS: set = set()


def _install_term_handler(loop, stopper) -> None:
    """SIGTERM -> clean daemon stop (the reference's handle_osd_signal);
    SIGKILL needs no handler — that's the crash path tests exercise."""

    def _term():
        task = asyncio.ensure_future(stopper())
        _TERM_TASKS.add(task)
        task.add_done_callback(_TERM_TASKS.discard)

    loop.add_signal_handler(signal.SIGTERM, _term)


async def _run_forever(stop_evt: asyncio.Event) -> None:
    await stop_evt.wait()


def daemon_main(kind: str, ident: int, spec_path: str) -> None:
    """Shared entry point behind ``python -m ceph_tpu.{mon,osd}``."""
    import jax

    # placement needs exact 64-bit CRUSH math: one switch at start-up,
    # before any kernel traces, so EC and CRUSH share one set of dtype rules
    jax.config.update("jax_enable_x64", True)
    spec = ClusterSpec.load(spec_path)
    from ceph_tpu.common.kv import FileDB

    async def amain() -> None:
        loop = asyncio.get_event_loop()
        stop_evt = asyncio.Event()
        cfg = spec.build_config()
        keyring = spec.bytes_keyring()
        db = None
        if kind in ("mon", "osd"):
            if (
                kind == "osd"
                and cfg.get("osd_objectstore") == "memstore"
            ):
                from ceph_tpu.common.kv import MemDB

                db = MemDB()
            else:
                # kstore-file AND blockstore both persist through this
                # FileDB; a blockstore OSD adds its block file inside
                # the same per-daemon dir (OSDService builds the store
                # from osd_objectstore)
                db = FileDB(
                    os.path.join(spec.run_dir, f"{kind}.{ident}.kv")
                )
        if kind == "mon":
            from ceph_tpu.mon import Monitor

            mon = Monitor(
                ident,
                spec.monmap(),
                spec.initial_osdmap(),
                db=db,
                config=cfg,
                keyring=keyring,
            )
            await mon.start()

            async def _stop():
                await mon.stop()
                stop_evt.set()

            _install_term_handler(loop, _stop)
            print(f"mon.{ident} up at {spec.mon_addrs[ident]}", flush=True)
        elif kind == "osd":
            from ceph_tpu.osd.daemon import OSDService

            osd = OSDService(
                ident, spec.monmap(), db=db, config=cfg, keyring=keyring
            )
            # the reference OSD dlopens every cls plugin at boot; a
            # daemon-main OSD registers all built-in class families so
            # MDS/RGW/journal consumers work against any process
            from ceph_tpu.cephfs.fs import register_fs_classes
            from ceph_tpu.journal.journal import (
                register_journal_classes,
            )
            from ceph_tpu.rgw.gateway import register_rgw_classes

            register_fs_classes(osd)
            register_journal_classes(osd)
            register_rgw_classes(osd)
            await osd.start()

            async def _stop():
                await osd.stop()
                stop_evt.set()

            _install_term_handler(loop, _stop)
            print(
                f"osd.{ident} up at {osd.messenger.my_addr}, encode "
                f"path {osd.encode_service.device_path()}",
                flush=True,
            )
        elif kind == "mds":
            from ceph_tpu.cephfs.mds import MDSService

            mds = MDSService(
                f"mds.{ident}", spec.monmap(),
                int(spec.extras.get("mds_data_pool", 1)),
                config=cfg, keyring=keyring,
            )
            await mds.start()

            async def _stop():
                await mds.stop()
                stop_evt.set()

            _install_term_handler(loop, _stop)
            print(f"mds.{ident} up at {mds.addr}", flush=True)
        elif kind == "rgw":
            from ceph_tpu.rados.client import IoCtx, Rados
            from ceph_tpu.rgw import ObjectGateway, S3Frontend

            rados = Rados(
                f"client.rgw{ident}", spec.monmap(), config=cfg,
                keyring=keyring,
            )
            await rados.connect()
            gw = ObjectGateway(
                IoCtx(rados.objecter,
                      int(spec.extras.get("rgw_data_pool", 2))),
                index_ioctx=IoCtx(
                    rados.objecter,
                    int(spec.extras.get("rgw_index_pool", 1)),
                ),
            )
            users = dict(spec.extras.get("rgw_users") or {})
            front = S3Frontend(gw, users=users)
            port = await front.start()
            # the kernel-assigned port is published for the launcher
            # (vstart.sh writes the same kind of run files); one tiny
            # write at boot, before any IO is served
            with open(  # cephlint: disable=async-blocking
                os.path.join(spec.run_dir, f"rgw.{ident}.port"), "w"
            ) as f:
                f.write(str(port))

            async def _stop():
                await front.stop()
                await rados.shutdown()
                stop_evt.set()

            _install_term_handler(loop, _stop)
            print(f"rgw.{ident} serving S3 on :{port}", flush=True)
        elif kind == "mgr":
            from ceph_tpu.mgr.daemon import MgrService

            mgr = MgrService(
                f"mgr.{ident}", spec.monmap(), config=cfg,
                keyring=keyring,
            )
            await mgr.start()
            port = await mgr.serve_http()
            # boot-time run-file write, before any IO is served
            with open(  # cephlint: disable=async-blocking
                os.path.join(spec.run_dir, f"mgr.{ident}.port"), "w"
            ) as f:
                f.write(str(port))

            async def _stop():
                await mgr.stop()
                stop_evt.set()

            _install_term_handler(loop, _stop)
            print(f"mgr.{ident} http on :{port}", flush=True)
        else:  # pragma: no cover - guarded by argparse choices
            raise SystemExit(f"unknown daemon kind {kind!r}")
        await _run_forever(stop_evt)

    if os.environ.get("CEPH_TPU_PROFILE"):
        import cProfile

        prof = cProfile.Profile()
        prof.enable()
        try:
            asyncio.run(amain())
        finally:
            prof.disable()
            prof.dump_stats(
                os.path.join(spec.run_dir, f"{kind}.{ident}.prof")
            )
    else:
        asyncio.run(amain())


# ---------------------------------------------------------------------------
# The launcher


class VStart:
    """Boot + manage a multi-process cluster from the test/bench process.

    ``start()`` spawns one interpreter per daemon; ``kill_osd`` delivers a
    real signal (default SIGKILL — the crash the thrasher wants);
    ``start_osd`` boots a fresh process for an id over the daemon's
    surviving FileDB, which is the reference's restart-with-intact-store
    path.
    """

    def __init__(
        self,
        run_dir: str,
        n_mons: int = 3,
        n_osds: int = 4,
        config: dict | None = None,
        env: dict | None = None,
    ):
        os.makedirs(run_dir, exist_ok=True)
        cfg = {
            "mon_lease": 0.25,
            "mon_election_timeout": 1.0,
            "osd_heartbeat_interval": 0.25,
            # daemons no longer share a loop: grace can be much tighter
            # than the in-process tier's jit-compile-absorbing 2s
            "osd_heartbeat_grace": 3,
            # keep every daemon's Unix sockets + ring files inside the
            # cluster's run_dir so teardown removes them with the dir
            "ms_uds_dir": run_dir,
        }
        cfg.update(config or {})
        ports = pick_ports(n_mons)
        self.spec = ClusterSpec(
            mon_addrs=[("127.0.0.1", p) for p in ports],
            n_osds=n_osds,
            run_dir=run_dir,
            config=cfg,
        )
        self.spec_path = os.path.join(run_dir, "cluster_spec.json")
        self.spec.save(self.spec_path)
        # a chip belongs to one process: the daemons encode on the CPU
        # until one chip-owning encode server per host serves them
        self.env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.env.update(env or {})
        self.mons: dict[int, subprocess.Popen] = {}
        self.osds: dict[int, subprocess.Popen] = {}
        self.extra: dict[tuple, subprocess.Popen] = {}
        self._logs: list = []

    # -- process management ---------------------------------------------------

    #: daemon kind -> python module hosting its __main__
    _KIND_MODULE = {
        "mon": "ceph_tpu.mon",
        "osd": "ceph_tpu.osd",
        "mds": "ceph_tpu.cephfs",
        "rgw": "ceph_tpu.rgw",
        "mgr": "ceph_tpu.mgr",
    }

    def _spawn(self, kind: str, ident: int) -> subprocess.Popen:
        log = open(
            os.path.join(self.spec.run_dir, f"{kind}.{ident}.log"), "ab"
        )
        self._logs.append(log)
        return subprocess.Popen(
            [
                sys.executable,
                "-m",
                self._KIND_MODULE[kind],
                "--id",
                str(ident),
                "--spec",
                self.spec_path,
            ],
            stdout=log,
            stderr=subprocess.STDOUT,
            env=self.env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )

    def start(self) -> None:
        for r in range(len(self.spec.mon_addrs)):
            self.mons[r] = self._spawn("mon", r)
        for i in range(self.spec.n_osds):
            self.osds[i] = self._spawn("osd", i)

    def start_osd(self, osd_id: int) -> None:
        self.osds[osd_id] = self._spawn("osd", osd_id)

    def start_daemon(self, kind: str, ident: int) -> None:
        """Spawn an mds/rgw/mgr process (their pools must exist first —
        the vstart.sh ordering). Pool bindings/users ride spec.extras."""
        self.extra[(kind, ident)] = self._spawn(kind, ident)

    def daemon_port(self, kind: str, ident: int,
                    timeout: float = 60.0) -> int:
        """Kernel-assigned port an rgw/mgr daemon published in its run
        file (vstart.sh's out-dir convention)."""
        path = os.path.join(
            self.spec.run_dir, f"{kind}.{ident}.port"
        )
        end = time.time() + timeout
        while time.time() < end:
            try:
                with open(path) as f:
                    raw = f.read().strip()
                if raw:
                    return int(raw)
            except FileNotFoundError:
                pass
            time.sleep(0.2)
        raise TimeoutError(f"{kind}.{ident} never published a port")

    def kill_osd(self, osd_id: int, sig: int = signal.SIGKILL) -> None:
        p = self.osds.pop(osd_id)
        p.send_signal(sig)
        p.wait(timeout=30)

    def kill_mon(self, rank: int, sig: int = signal.SIGKILL) -> None:
        p = self.mons.pop(rank)
        p.send_signal(sig)
        p.wait(timeout=30)

    def stop(self) -> None:
        procs = (
            list(self.mons.values()) + list(self.osds.values())
            + list(self.extra.values())
        )
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGKILL)
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:  # pragma: no cover
                p.kill()
        for log in self._logs:
            log.close()
        self.mons.clear()
        self.osds.clear()
        self.extra.clear()

    # -- client-side helpers --------------------------------------------------

    def client(self, name: str = "client.admin"):
        from ceph_tpu.rados.client import Rados

        return Rados(name, self.spec.monmap(), config=self.spec.build_config())

    async def wait_healthy(
        self, rados=None, osds: set | None = None, timeout: float = 60.0
    ):
        """Wait until the committed osdmap shows every expected OSD up."""
        own = rados is None
        if own:
            rados = self.client()
            await rados.connect()
        want = osds if osds is not None else set(range(self.spec.n_osds))
        loop = asyncio.get_event_loop()
        end = loop.time() + timeout
        try:
            while True:
                m = rados.objecter.osdmap
                if m is not None and all(
                    i < m.max_osd and m.osd_up[i] for i in want
                ):
                    return m
                if loop.time() > end:
                    raise TimeoutError(
                        f"osds {want} not up; map={None if m is None else m.epoch}"
                    )
                await asyncio.sleep(0.1)
        finally:
            if own:
                await rados.shutdown()


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(
        description="boot a multi-process cluster (vstart.sh role)"
    )
    ap.add_argument("--run-dir", default="./vstart-run")
    ap.add_argument("--mons", type=int, default=3)
    ap.add_argument("--osds", type=int, default=4)
    args = ap.parse_args(argv)
    v = VStart(args.run_dir, n_mons=args.mons, n_osds=args.osds)
    v.start()
    print(f"spec: {v.spec_path}")
    print(f"mons: {[p.pid for p in v.mons.values()]}")
    print(f"osds: {[p.pid for p in v.osds.values()]}")
    try:
        asyncio.run(v.wait_healthy())
        print("HEALTH_OK: all osds up — ^C to tear down")
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        v.stop()


if __name__ == "__main__":
    main()
