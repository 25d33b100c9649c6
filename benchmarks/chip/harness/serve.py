"""The measured window: clients drive ``serving.gateway.EngineRunner`` (the
engine thread HTTP users reach, polling ``ServeEngine`` in overlap mode)
through ``submit(req, sink)``, and every token is timed where the client
receives it.

Closed loop: each client sends its next request from the sink of its
previous one's last token.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

import jax

from harness.traffic import RequestSpec


@dataclasses.dataclass
class Record:
    spec: RequestSpec
    client: int
    due: float = 0.0  # perf_counter seconds, when the client sent it
    sent: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish: Optional[str] = None
    error: Optional[str] = None


class Clients:
    """Builds requests from specs, sends them, and records what comes
    back."""

    def __init__(self, runner, traffic):
        from repro.serving import Request, SamplingParams

        self._Request, self._SP = Request, SamplingParams
        self.runner = runner
        self.traffic = traffic
        self.sampling = traffic.sampling
        self.records: List[Record] = []
        self._lock = threading.Lock()
        self._open = True  # closed loop: clients keep sending while set
        self._pool: List[RequestSpec] = []

    def _request(self, spec: RequestSpec):
        sp = (self._SP(temperature=0.0, max_new=spec.max_new) if spec.greedy
              else self._SP(temperature=self.sampling["temperature"],
                            top_k=self.sampling["top_k"],
                            top_p=self.sampling.get("top_p", 1.0),
                            max_new=spec.max_new))
        return self._Request(prompt=list(spec.prompt), sampling=sp)

    def send(self, spec: RequestSpec, client: int, due: float) -> Record:
        rec = Record(spec=spec, client=client, due=due)
        with self._lock:
            self.records.append(rec)

        def sink(out, rec=rec):
            now = time.perf_counter()
            with jax.profiler.TraceAnnotation("client.sink"):
                if out.token is not None:
                    rec.times.append(now)
                    rec.tokens.append(int(out.token))
                if out.finished:
                    rec.finish, rec.error = out.finish_reason, out.error
                    if self._open:
                        self._next(rec.client)

        with jax.profiler.TraceAnnotation("client.submit"):
            req = self._request(spec)
            rec.sent = time.perf_counter()
            self.runner.submit(req, sink)
        return rec

    # closed loop -----------------------------------------------------------
    def _next(self, client: int) -> None:
        with self._lock:
            if not self._pool:
                self._pool = self.traffic.requests(64)
            spec = self._pool.pop(0)
        now = time.perf_counter()
        self.send(spec, client, now)

    def start_closed(self, wave: List[RequestSpec]) -> List[Record]:
        now = time.perf_counter()
        return [self.send(s, c, now) for c, s in enumerate(wave)]

    def stop(self) -> None:
        self._open = False


def wait_until(pred, timeout: float, step: float = 0.005) -> bool:
    end = time.perf_counter() + timeout
    while time.perf_counter() < end:
        if pred():
            return True
        time.sleep(step)
    return pred()
