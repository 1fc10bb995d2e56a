"""The cluster of one run: controller, broker, and the ONE server that
holds the chip, each a process of its own, started from a parent that
never imports jax. Copied from chip_smoke.py's `Cluster` (PR 21); the
server goes through server_launcher.py so that it can be traced."""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class HarnessFailure(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body=None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def series_delta(before: dict, after: dict, metric: str) -> float:
    return sum(v - before.get(k, 0) for k, v in after.items()
               if k == metric or k.startswith(metric + "{"))


class Cluster:
    """Every process it starts, it stops."""

    def __init__(self, work: str, cache_dir: str, rehearsal: bool):
        self.work = work
        self.procs = {}
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = ROOT + os.pathsep + \
            self.env.get("PYTHONPATH", "")
        self.env["TMPDIR"] = os.path.join(work, "tmp")
        # controller and broker import jax but must never start a backend:
        # with a platform that does not exist, one that tried would raise
        self.env["JAX_PLATFORMS"] = "no_chip_for_this_role"
        self.server_env = dict(self.env)
        # tpu, not "": without a chip JAX must raise, not hand back CPUs
        self.server_env["JAX_PLATFORMS"] = "cpu" if rehearsal else "tpu"
        self.server_env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        # persist every compile, so that only a checkout's first run does
        self.server_env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        self.server_env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
        self.coord_port = free_port()
        self.broker_port = free_port()
        self.coordinator = f"127.0.0.1:{self.coord_port}"
        self.profile_dir = os.path.join(work, "profile")
        self.admin_url = None

    def spawn(self, name: str, argv: list, env: dict) -> None:
        log = open(os.path.join(self.work, "logs", f"{name}.log"), "ab")
        self.procs[name] = subprocess.Popen(
            [sys.executable, *argv], env=env, cwd=ROOT, stdout=log,
            stderr=subprocess.STDOUT)
        log.close()

    def log_tail(self, name: str, n: int = 40) -> str:
        with open(os.path.join(self.work, "logs", f"{name}.log"),
                  errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def wait(self, predicate, what: str, timeout: float = 180.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for name, proc in self.procs.items():
                if proc.poll() is not None:
                    raise HarnessFailure(
                        f"{name} exited with {proc.returncode} while "
                        f"waiting for {what}:\n{self.log_tail(name)}")
            try:
                got = predicate()
            except (OSError, ValueError, KeyError):
                got = None
            if got:
                return got
            time.sleep(0.1)
        raise HarnessFailure(f"timed out after {timeout:.0f}s waiting for "
                             f"{what}")

    def spawn_all(self) -> None:
        """Controller, server, broker: started, not yet waited for."""
        admin = ["-m", "pinot_tpu.tools.admin"]
        self.spawn("controller", admin + [
            "StartController", "--state-dir",
            os.path.join(self.work, "state"), "--port", str(self.coord_port),
            "--deep-store", "file://" + os.path.join(self.work, "store")],
            self.env)
        # the server's imports take longest: start it beside the controller
        self.spawn("server", [
            os.path.join(BENCH, "server_launcher.py"),
            "--profile-dir", self.profile_dir, "--",
            "StartServer", "--instance-id", "server_0",
            "--coordinator", self.coordinator, "--tpu"], self.server_env)

        def controller_up():
            with socket.create_connection(("127.0.0.1", self.coord_port),
                                          timeout=1):
                return True
        self.wait(controller_up, "the controller")
        self.spawn("broker", admin + [
            "StartBroker", "--coordinator", self.coordinator,
            "--http-port", str(self.broker_port)], self.env)

    def wait_server(self) -> dict:
        """The server registered and holding its device; returns its
        /debug/device report."""
        from pinot_tpu.controller.coordination import CoordinationClient

        def admin_url():
            client = CoordinationClient(self.coordinator)
            try:
                inst = client.get_state()["instances"].get("server_0") or {}
            finally:
                client.close()
            url = inst.get("admin_url")
            return url if url and http_json(url + "/debug/device") else None
        self.admin_url = self.wait(admin_url, "the server to register")
        return self.device()

    def device(self) -> dict:
        return http_json(self.admin_url + "/debug/device")

    def stop(self, name: str) -> None:
        proc = self.procs.pop(name, None)
        if proc is None or proc.poll() is not None:
            return
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=15)

    def stop_all(self) -> None:
        for name in list(self.procs):
            self.stop(name)

    def counters(self) -> dict:
        """The server's /metrics, by series (`name{labels}`)."""
        with urllib.request.urlopen(self.admin_url + "/metrics",
                                    timeout=60) as r:
            text = r.read().decode()
        out = {}
        for line in text.splitlines():
            if line and not line.startswith("#"):
                series, _, value = line.rpartition(" ")
                out[series.removeprefix("pinot_tpu_server_")] = float(value)
        return out
