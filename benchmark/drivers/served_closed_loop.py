"""Traffic driver `served_closed_loop`: a serve.ScanServer in this process
(it holds the chip) and `clients` callers in processes of their own, each
its own tenant, each waiting for its reply before it sends the next
request, each walking a seeded shuffle of the cell's files.

The clients are serve_client.py, started as new interpreters with
JAX_PLATFORMS=cpu: they import the serve client, which loads no JAX, and
never touch the chip. They stamp requests with CLOCK_MONOTONIC, which the
processes of one machine share with this one.
"""
import json
import os
import subprocess
import sys
import time

from ..harness import BenchFault, device_proof, now

CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "serve_client.py")


class Client:
    def __init__(self, number: int, init: dict):
        import cobrix_tpu

        # the client imports the program from where this process found it
        program = os.path.dirname(os.path.dirname(cobrix_tpu.__file__))
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=program)
        self.number = number
        self.process = subprocess.Popen(
            [sys.executable, CLIENT], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, env=env, text=True)
        self.send(dict(init, cmd="init", number=number))

    def send(self, message: dict) -> None:
        self.process.stdin.write(json.dumps(message) + "\n")
        self.process.stdin.flush()

    def reply(self) -> dict:
        line = self.process.stdout.readline()
        if not line:
            raise BenchFault(f"client {self.number} ended without a reply "
                             f"(exit code {self.process.poll()})")
        return json.loads(line)

    def close(self) -> None:
        if self.process.poll() is None:
            try:
                self.send({"cmd": "exit"})
                self.process.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.process.kill()
        self.process.wait()
        self.process.stdin.close()
        self.process.stdout.close()


class Driver:
    def __init__(self, run):
        self.run = run
        self.server = None
        self.clients = []

    def set_up(self) -> None:
        from cobrix_tpu.serve import ScanServer

        run = self.run
        self.server = ScanServer(**run.traffic["server"]).start()
        init = {
            "address": list(self.server.address),
            "files": [{"path": f["path"], "bytes": f["bytes"],
                       "rows": f["facts"]["records"]} for f in run.files],
            "options": run.reader_options(), "seed": run.seed,
            "request_timeout_s": run.traffic["request_timeout_s"],
        }
        for number in range(run.traffic[run.scale]["clients"]):
            self.clients.append(Client(number, init))
        for client in self.clients:
            client.reply()

    def _all(self, messages: list) -> list:
        """Send each client its message, then gather the replies."""
        for client, message in zip(self.clients, messages):
            client.send(message)
        return [client.reply() for client in self.clients]

    def _judge(self, replies: list) -> list:
        run = self.run
        requests = []
        for number, reply in enumerate(replies):
            for request in reply["requests"]:
                request["client"] = number
                if request["ok"]:
                    wrong = device_proof(request.get("device"),
                                         run.device["platform"],
                                         run.device["first"])
                    request.update(ok=not wrong, error=wrong or None)
                requests.append(request)
        return sorted(requests, key=lambda r: r["sent"])

    def warm_up(self) -> list:
        """Each file once, one after the other from the first client: four
        callers at once share the server's interpreter lock and take over
        twice as long for the same sixteen requests (PERF.md, PR 23)."""
        first = self.clients[0]
        first.send({"cmd": "warm",
                    "files": list(range(len(self.run.files)))})
        return self._judge([first.reply()])

    def window(self, seconds: float) -> dict:
        run = self.run
        start = now() + 0.25
        for client in self.clients:
            client.send({"cmd": "window", "start": start,
                         "end": start + seconds})
        time.sleep(max(0.0, start - now()))
        run.tracer.start()
        if run.tracer.enabled:
            with run.tracer.span("bench.serve_window"):
                time.sleep(min(seconds, run.traffic["trace_seconds"]))
            run.tracer.stop()
        replies = [client.reply() for client in self.clients]
        return {"start": start, "requests": self._judge(replies)}

    def check(self, check_files) -> list:
        checked = check_files(self.run, {}, write_references=True)
        failures = list(checked["failures"])
        compared = 0
        for reply in self._all([{"cmd": "check",
                                 "references": checked["references"]}]
                               * len(self.clients)):
            failures += reply["failures"]
            compared += reply["compared"]
        if not compared:
            failures.append("no served table was compared")
        return failures

    def close(self) -> None:
        for client in self.clients:
            client.close()
        self.clients = []
        if self.server is not None:
            self.server.stop()
            self.server = None
