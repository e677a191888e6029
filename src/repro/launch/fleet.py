"""Fleet launcher: a self-healing partitioned Knowledge-Bank deployment in
one command.

  PYTHONPATH=src python -m repro.launch.fleet --partitions 2 --replicas 1 \
      --makers "graph_builder x8" --seconds 30

Boots, supervises, and tears down the whole cross-process CARLS serving
side:

1. N partition members — ``serve.py --kb --kb-join p/N --listen host:0``,
   one process each, ephemeral ports parsed from their "listening on"
   lines (the GLOBAL bank size is ``--kb-entries``; each member hosts only
   its consistent-hash slice).
2. With ``--replicas 1``, one standby per member — ``serve.py --kb-join
   p/N --replica-of host:port_p``: the standby boot-copies the primary's
   full row state (every leaf, bit-identically) and serves beside it.
   Clients dial the fleet with the ``host:p0|host:s0,...`` --kb-connect
   syntax; their routers attach the standbys and promote one when its
   primary dies — the fleet heals without a restart.
3. Maker packs — ``--makers "graph_builder x8"`` (comma list for several
   kinds) spawns that many ``maker_worker`` processes per kind, each
   pinned to ``--node-slice i/M``. Against this fleet the slices follow
   the ring (``KBRouter.partition_slices``), so every maker batch lands on
   a single member: the router's no-copy fast path.

The supervisor loop restarts makers that CRASH (non-zero exit; a clean
--steps/--seconds exit stays down) and logs member deaths — a member with
a standby needs no restart, its clients promote. SIGINT/SIGTERM (or
``--seconds``) tears everything down makers-first and prints per-child
exit codes plus the restart count.

The connect spec is printed on boot (``fleet ready: --kb-connect ...``) so
trainers can attach: ``launch/train.py --makers ... --kb-connect <spec>``.

Chips: a chip belongs to one process. On a TPU host every serving process
(member or standby) gets a chip of its own (``ChipPlan``), and a layout
with more serving processes than chips fails before anything starts.
Maker workers are clients of the bank and run pinned to the CPU. With
``JAX_PLATFORMS=cpu`` set, or no chip on the host, everything runs on the
CPU. The supervisor itself never imports JAX.
"""
from __future__ import annotations

import argparse
import glob
import os
import re
import select
import signal
import subprocess
import sys
import time

STARTUP_TIMEOUT_S = 300         # cold jax import + jit warmup per child
TPU_PROCESS_PORT_BASE = 8476    # libtpu's own per-process port, + chip


def host_chips() -> int:
    """TPU chips on this host, counted from their device files so the
    caller never imports JAX; 0 when the caller pinned JAX to the CPU."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return 0
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


class ChipPlan:
    """One chip per serving process; client-only processes on the CPU.

    ``serving_env`` hands out the host's chips in order and raises once
    they run out, so two processes never race for one chip. With no chips
    every child is pinned to the CPU."""

    def __init__(self, n_chips: int):
        self.n_chips = n_chips
        self.assigned = 0

    def require(self, n_serving: int) -> None:
        """Fail at boot if ``n_serving`` processes cannot each own a chip."""
        if self.n_chips and n_serving > self.n_chips:
            raise RuntimeError(
                f"{n_serving} serving processes but {self.n_chips} chips on "
                "this host: each needs a chip of its own (shrink the "
                "layout, or pin the fleet to the CPU with JAX_PLATFORMS=cpu)")

    def serving_env(self, env: dict) -> dict:
        env = dict(env)
        if not self.n_chips:
            env["JAX_PLATFORMS"] = "cpu"
            return env
        self.require(self.assigned + 1)
        chip = self.assigned
        self.assigned += 1
        env.update(TPU_VISIBLE_CHIPS=str(chip),
                   TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                   TPU_PROCESS_BOUNDS="1,1,1",
                   TPU_PROCESS_PORT=str(TPU_PROCESS_PORT_BASE + chip))
        return env

    @staticmethod
    def client_env(env: dict) -> dict:
        env = dict(env)
        env["JAX_PLATFORMS"] = "cpu"
        return env


def _parse_maker_packs(spec: str):
    """'graph_builder x8,embedding_refresh x2' -> [(kind, count), ...]"""
    packs = []
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        m = re.fullmatch(r"(\w+)(?:\s*x\s*(\d+))?", item)
        if not m:
            raise ValueError(f"bad --makers pack {item!r} "
                             "(want 'kind' or 'kind xN')")
        packs.append((m.group(1), int(m.group(2) or 1)))
    return packs


class Fleet:
    def __init__(self, args):
        self.args = args
        self.members = []       # (proc, port) per partition, ring order
        self.standbys = []      # (proc, port) or None per partition
        self.makers = []        # dicts: proc / cmd / name / restarts
        self.maker_restarts = 0
        self._dead_members = set()
        self.env = dict(os.environ)
        root = os.getcwd()
        src = os.path.join(root, "src")
        if os.path.isdir(src):
            self.env["PYTHONPATH"] = (src + os.pathsep
                                      + self.env.get("PYTHONPATH", ""))
        self.chips = ChipPlan(host_chips())
        self.maker_env = ChipPlan.client_env(self.env)

    # -- child bootstrapping ----------------------------------------------

    def _serve_cmd(self, slot: int, extra):
        a = self.args
        return [sys.executable, "-m", "repro.launch.serve", "--kb",
                "--kb-entries", str(a.kb_entries), "--kb-dim",
                str(a.kb_dim), "--kb-storage", a.kb_storage,
                "--seed", str(a.seed),
                "--kb-join", f"{slot}/{a.partitions}",
                "--listen", f"{a.host}:0", "--serve-seconds", "0",
                *extra]

    def _boot_server(self, cmd, name):
        """Start a serve.py child; return (proc, port) once it reports
        listening — select with a deadline, so a wedged child fails at the
        startup budget with its output attached, not silently."""
        proc = subprocess.Popen(cmd, env=self.chips.serving_env(self.env),
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)
        lines = []
        deadline = time.time() + STARTUP_TIMEOUT_S
        while True:
            if time.time() > deadline:
                proc.kill()
                raise RuntimeError(
                    f"{name} never reported listening within "
                    f"{STARTUP_TIMEOUT_S}s:\n" + "".join(lines))
            ready, _, _ = select.select([proc.stdout], [], [], 5.0)
            if not ready:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"{name} exited early:\n{''.join(lines)}")
                continue
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError(f"{name} exited early:\n"
                                   + "".join(lines))
            lines.append(line)
            print(f"[{name}]", line, end="", flush=True)
            m = re.search(r"listening on [\d.]+:(\d+)", line)
            if m:
                return proc, int(m.group(1))

    def connect_spec(self) -> str:
        legs = []
        for p, (_, port) in enumerate(self.members):
            leg = f"{self.args.host}:{port}"
            if self.standbys[p] is not None:
                leg += f"|{self.args.host}:{self.standbys[p][1]}"
            legs.append(leg)
        return ",".join(legs)

    def _maker_cmd(self, kind: str, idx: int, total: int):
        a = self.args
        cmd = [sys.executable, "-m", "repro.launch.maker_worker",
               "--connect", self.connect_spec(), "--makers", kind,
               "--node-slice", f"{idx}/{total}",
               "--batch", str(a.maker_batch), "--steps",
               str(a.maker_steps), "--period", str(a.maker_period),
               "--seed", str(a.seed + idx),
               "--client-name", f"fleet-{kind}-{idx}"]
        if a.ckpt_dir:
            cmd += ["--ckpt-dir", a.ckpt_dir, "--arch", a.arch]
        return cmd

    def start(self):
        a = self.args
        self.chips.require(a.partitions * (1 + a.replicas))
        for p in range(a.partitions):
            self.members.append(self._boot_server(
                self._serve_cmd(p, []), f"p{p}"))
            self.standbys.append(None)
        if a.replicas:
            for p, (_, port) in enumerate(self.members):
                self.standbys[p] = self._boot_server(
                    self._serve_cmd(
                        p, ["--replica-of", f"{a.host}:{port}"]),
                    f"s{p}")
        print(f"fleet ready: --kb-connect {self.connect_spec()}",
              flush=True)
        for kind, count in _parse_maker_packs(a.makers):
            for i in range(count):
                cmd = self._maker_cmd(kind, i, count)
                self.makers.append({
                    "name": f"{kind}-{i}", "cmd": cmd,
                    "proc": subprocess.Popen(cmd, env=self.maker_env),
                    "restarts": 0})
        if self.makers:
            print(f"fleet makers: {len(self.makers)} workers", flush=True)

    # -- supervision -------------------------------------------------------

    def supervise_once(self):
        """One supervision tick: restart crashed makers, log member
        deaths (standby-backed members heal client-side — no restart)."""
        for m in self.makers:
            rc = m["proc"].poll()
            if rc is None or rc == 0:
                continue
            m["restarts"] += 1
            self.maker_restarts += 1
            print(f"fleet: maker {m['name']} crashed (exit {rc}), "
                  f"restarting (x{m['restarts']})", flush=True)
            m["proc"] = subprocess.Popen(m["cmd"], env=self.maker_env)
        for p, (proc, port) in enumerate(self.members):
            if proc.poll() is not None and p not in self._dead_members:
                self._dead_members.add(p)
                sb = ("standby takes over on the next client request"
                      if self.standbys[p] is not None
                      else "NO standby — clients owning its rows fail")
                print(f"fleet: member p{p} ({self.args.host}:{port}) "
                      f"exited {proc.returncode}; {sb}", flush=True)

    def shutdown(self):
        """Makers first (they dial the members), then the bank fleet."""
        for m in self.makers:
            if m["proc"].poll() is None:
                m["proc"].send_signal(signal.SIGTERM)
        for m in self.makers:
            try:
                m["proc"].wait(timeout=60)
            except subprocess.TimeoutExpired:
                m["proc"].kill()
        for group in (self.standbys, self.members):
            for item in group:
                if item is None:
                    continue
                proc, _ = item
                if proc.poll() is None:
                    proc.send_signal(signal.SIGTERM)
        for group, tag in ((self.standbys, "s"), (self.members, "p")):
            for i, item in enumerate(group):
                if item is None:
                    continue
                proc, _ = item
                try:
                    out, _ = proc.communicate(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    out = ""
                if out:
                    print(f"[{tag}{i}]", out, flush=True)
        print(f"fleet done: {len(self.members)} members, "
              f"{sum(s is not None for s in self.standbys)} standbys, "
              f"{len(self.makers)} makers "
              f"({self.maker_restarts} restarts)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--partitions", type=int, default=2,
                    help="fleet members (consistent-hash ring slots)")
    ap.add_argument("--replicas", type=int, default=0, choices=[0, 1],
                    help="standbys per member (serve.py --replica-of): "
                         "routers promote one when its primary dies")
    ap.add_argument("--makers", default="",
                    help="maker packs, e.g. 'graph_builder x8' or "
                         "'embedding_refresh x4,graph_builder x2' — each "
                         "pack spawns count maker_worker processes with "
                         "ring-aligned --node-slice i/count")
    ap.add_argument("--kb-entries", type=int, default=4096,
                    help="GLOBAL bank rows (split across members)")
    ap.add_argument("--kb-dim", type=int, default=64)
    ap.add_argument("--kb-storage", choices=["fp32", "int8"],
                    default="fp32")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--maker-batch", type=int, default=64)
    ap.add_argument("--maker-steps", type=int, default=0,
                    help="per-worker step cap (0 = run until shutdown)")
    ap.add_argument("--maker-period", type=float, default=0.0,
                    help="per-maker pacing floor in seconds")
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint dir for ckpt-loading maker kinds")
    ap.add_argument("--arch", default="yi-6b",
                    help="model arch for ckpt-loading maker kinds")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="run this long then tear down "
                         "(0 = until SIGINT/SIGTERM)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    fleet = Fleet(args)
    stop = {"flag": False}
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.update(flag=True))
    try:
        fleet.start()
        deadline = (time.time() + args.seconds) if args.seconds else None
        while not stop["flag"]:
            if deadline is not None and time.time() > deadline:
                break
            fleet.supervise_once()
            time.sleep(0.2)
    finally:
        fleet.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
