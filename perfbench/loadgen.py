"""Traffic for the benchmark: one general generator and one client.

A traffic mix is a data file under ``perfbench/traffic/``; nothing here
knows a mix by name. ``build_deck`` turns the file's parameters and
``--seed`` into the requests of a run, ``build_corpus`` into the
documents ingested during set-up. **The seed shuffles; it does not
resize**: the multiset of question lengths, answer budgets, chunk
lengths and (for ``poisson``) inter-arrival gaps is fixed by the file,
the seed chooses the texts and the order.

The client is stdlib only (``http.client`` + threads) so the parent
process that drives the server child never imports jax. Every content
frame's arrival is stamped with ``time.monotonic()``; a request is timed
from its send in a closed loop and from its *due* instant in an open
loop, and the generator's lateness is reported.

Copied in spirit from ``tools/loadgen`` (seeded schedules, SSE timing);
see PERF.md Open questions for what a later PR deletes there.
"""
from __future__ import annotations

import http.client
import itertools
import json
import random
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

# A fixed vocabulary of plain ASCII words. Texts are words joined by one
# space, so the whitespace splitter of the chain returns a short
# document as ONE chunk with exactly the bytes it was given.
WORDS = (
    "cooling loop scheduler admission wave interconnect topology routing "
    "checkpoint resume vector index compaction tokenizer fallback tracing "
    "span export batch quantization scale layout page cache prefix kernel "
    "matrix stream decode prefill window budget limit margin sensor valve "
    "pump rack fabric link shard replica quorum ledger commit replay audit "
    "signal carrier filter buffer queue worker tenant region zone meter"
).split()

GAP_POOL_SEED = 20240601  # poisson: one fixed multiset of gaps for every --seed


def text_of_bytes(rng: random.Random, n: int, lead: str = "") -> str:
    """ASCII words joined by single spaces, exactly ``n`` bytes long,
    starting with ``lead`` (which makes texts distinct)."""
    if n <= 0:
        return ""
    out = lead
    while len(out) < n:
        out += (" " if out else "") + rng.choice(WORDS)
    out = out[:n]
    if out.endswith(" "):  # keep the byte count: a trailing space would be stripped
        out = out[:-1] + "x"
    return out


def _multiset(values: List[int], count: int) -> List[int]:
    """``count`` items cycling through ``values``: equal shares."""
    return [values[i % len(values)] for i in range(count)]


def build_corpus(traffic: Dict[str, Any], seed: int) -> List[Tuple[str, str]]:
    """(filename, text) for each document of the mix. One document is one
    chunk; its byte length comes from the fixed multiset ``chunk_bytes``."""
    spec = traffic.get("corpus")
    if not spec:
        return []
    rng = random.Random(seed * 7919 + 1)
    sizes = _multiset(list(spec["chunk_bytes"]), int(spec["documents"]))
    rng.shuffle(sizes)
    docs = []
    for i, n in enumerate(sizes):
        lead = f"document {i:03d} section {rng.randrange(10 ** 6):06d}"
        docs.append((f"perfbench_doc_{i:03d}.txt", text_of_bytes(rng, n, lead)))
    return docs


def build_deck(traffic: Dict[str, Any], seed: int, size: int = 0) -> List[Dict[str, Any]]:
    """The requests of a run, in order. The deck is a sequence of blocks,
    each holding every (question length, answer budget) pair once in an
    order the seed chooses: whatever prefix of the deck a run consumes
    carries the pairs in equal shares (to within one block), so two seeds
    do the same work. ``size`` (rounded up to whole blocks) defaults to
    four times the pairs times the clients, several times what one run
    consumes, and the deck cycles if it is ever exhausted."""
    pairs = list(itertools.product(traffic["question_bytes"], traffic["max_tokens"]))
    clients = int(traffic.get("clients", traffic.get("max_in_flight", 16)))
    if not size:
        size = len(pairs) * max(8, clients) * 4
    rng = random.Random(seed * 104729 + 7)
    deck = []
    for _ in range(-(-size // len(pairs))):
        block = list(pairs)
        rng.shuffle(block)
        deck.extend({"question_bytes": qb, "max_tokens": mt} for qb, mt in block)
    for i, item in enumerate(deck):
        lead = f"question {i:04d} about item {rng.randrange(10 ** 6):06d}:"
        item["question"] = text_of_bytes(rng, item["question_bytes"], lead)
    return deck


def arrival_times(traffic: Dict[str, Any], seed: int, horizon_s: float) -> List[float]:
    """Open loop: ``rate x horizon`` due instants. The gaps are one fixed
    multiset (drawn once from ``GAP_POOL_SEED``); the seed permutes them,
    so every seed offers the same arrivals in another order."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(rate * horizon_s))
    pool_rng = random.Random(GAP_POOL_SEED)
    gaps = [pool_rng.expovariate(rate) for _ in range(n)]
    random.Random(seed * 31337 + 3).shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g
        out.append(t)
    return out


# --------------------------------------------------------------------------- #
# Client


class RequestLog:
    """What the client saw of one request. Times are ``time.monotonic()``."""

    __slots__ = (
        "client", "index", "question_bytes", "max_tokens", "due_t", "send_t",
        "send_wall", "frame_t", "end_t", "status", "http_status", "error",
        "warnings", "chars", "turn",
    )

    def __init__(self, client: int, index: int, item: Dict[str, Any], due_t: Optional[float]):
        self.client = client
        self.index = index
        self.question_bytes = item["question_bytes"]
        self.max_tokens = item["max_tokens"]
        self.due_t = due_t
        self.send_t = 0.0
        self.send_wall = 0.0
        self.frame_t: List[float] = []
        self.end_t: Optional[float] = None
        self.status = "in_flight"  # ok | failed | in_flight
        self.http_status = 0
        self.error = ""
        self.warnings: List[str] = []
        self.chars = 0
        self.turn = 0

    def to_json(self, t_origin: float) -> Dict[str, Any]:
        rel = lambda t: None if t is None else round(t - t_origin, 6)  # noqa: E731
        return {
            "client": self.client, "index": self.index, "turn": self.turn,
            "question_bytes": self.question_bytes, "max_tokens": self.max_tokens,
            "due_s": rel(self.due_t), "send_s": rel(self.send_t),
            "send_wall": round(self.send_wall, 6), "end_s": rel(self.end_t),
            "status": self.status, "http_status": self.http_status,
            "error": self.error, "warnings": self.warnings, "chars": self.chars,
            "frames_s": [round(t - t_origin, 6) for t in self.frame_t],
        }


class Client:
    """Runs the requests of a mix against one server and keeps their logs."""

    def __init__(self, host: str, port: int, traffic: Dict[str, Any],
                 deck: List[Dict[str, Any]], canned: Tuple[str, ...] = ()):
        self.host, self.port = host, port
        self.traffic = traffic
        self.deck = deck
        self.canned = set(canned)
        self.logs: List[RequestLog] = []
        self._lock = threading.Lock()
        self._next = 0
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._conns: Dict[int, http.client.HTTPConnection] = {}
        self.first_done = threading.Semaphore(0)
        self.lateness_s: List[float] = []

    # -- one request -------------------------------------------------------- #
    def _take(self) -> Tuple[int, Dict[str, Any]]:
        with self._lock:
            i = self._next
            self._next += 1
        return i, self.deck[i % len(self.deck)]

    def one_request(self, client: int, item_index: int, item: Dict[str, Any],
                    history: Optional[List[Dict[str, str]]] = None,
                    due_t: Optional[float] = None, turn: int = 0,
                    answer_box: Optional[List[str]] = None) -> RequestLog:
        log = RequestLog(client, item_index, item, due_t)
        log.turn = turn
        with self._lock:
            self.logs.append(log)
        body = dict(self.traffic["request"])
        body["messages"] = (history or []) + [{"role": "user", "content": item["question"]}]
        body["max_tokens"] = item["max_tokens"]
        payload = json.dumps(body).encode()
        key = threading.get_ident()
        conn = http.client.HTTPConnection(self.host, self.port, timeout=600)
        self._conns[key] = conn
        answer: List[str] = []
        done_seen = False
        try:
            log.send_wall = time.time()
            log.send_t = time.monotonic()
            conn.request("POST", "/generate", body=payload,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            log.http_status = resp.status
            if resp.status != 200:
                log.error = f"http {resp.status}: {resp.read(300)!r}"
            else:
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    if not line.startswith(b"data: "):
                        continue
                    now = time.monotonic()
                    try:
                        frame = json.loads(line[6:])
                    except ValueError:
                        continue
                    for w in frame.get("warnings") or []:
                        log.warnings.append(str(w))
                    for choice in frame.get("choices", []):
                        content = choice.get("message", {}).get("content", "")
                        if content:
                            log.frame_t.append(now)
                            log.chars += len(content)
                            answer.append(content)
                        if choice.get("finish_reason") == "[DONE]":
                            done_seen = True
        except (OSError, http.client.HTTPException) as exc:
            if not self._stop.is_set():
                log.error = f"{type(exc).__name__}: {exc}"
        finally:
            log.end_t = time.monotonic()
            self._conns.pop(key, None)
            conn.close()
        if self._stop.is_set() and not done_seen:
            log.status = "in_flight"  # cut by the end of the run, not a failure
            log.end_t = None
        elif log.error or log.warnings or not done_seen:
            log.status = "failed"
            if not log.error:
                log.error = "warning frame" if log.warnings else "stream ended without [DONE]"
        elif "".join(answer).strip() in self.canned:
            log.status = "failed"
            log.error = "canned error answer"
        else:
            log.status = "ok"
        if answer_box is not None:
            answer_box.append("".join(answer))
        return log

    # -- loops -------------------------------------------------------------- #
    def _closed_loop(self, client: int, start_at: float) -> None:
        delay = start_at - time.monotonic()
        if delay > 0 and self._stop.wait(delay):
            return
        first = True
        while not self._stop.is_set():
            i, item = self._take()
            log = self.one_request(client, i, item)
            if log.status == "failed":
                self._stop.wait(0.5)  # a failing server is not hammered
            if first:
                self.first_done.release()
                first = False

    def _session_loop(self, client: int, start_at: float) -> None:
        delay = start_at - time.monotonic()
        if delay > 0 and self._stop.wait(delay):
            return
        first = True
        turns = int(self.traffic.get("turns", 4))
        while not self._stop.is_set():
            history: List[Dict[str, str]] = []
            for turn in range(turns):
                if self._stop.is_set():
                    break
                i, item = self._take()
                answer_box: List[str] = []
                log = self.one_request(client, i, item, history=history, turn=turn,
                                       answer_box=answer_box)
                if first:
                    self.first_done.release()
                    first = False
                if log.status != "ok":
                    break
                history = history + [
                    {"role": "user", "content": item["question"]},
                    {"role": "assistant", "content": (answer_box[0][:256] if answer_box else "") or "ok"},
                ]

    def _open_loop(self, t_zero: float, due: List[float]) -> None:
        """Dispatcher thread: start one worker per due instant (bounded by
        ``max_in_flight``), recording how late each start ran."""
        limit = threading.Semaphore(int(self.traffic.get("max_in_flight", 64)))
        workers: List[threading.Thread] = []

        def work(k, i, item, due_t):
            try:
                self.one_request(-1, i, item, due_t=due_t)
            finally:
                limit.release()
                if k == 0:  # the window opens once the first arrival is answered
                    self.first_done.release()

        for k, at in enumerate(due):
            due_t = t_zero + at
            delay = due_t - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                break
            if self._stop.is_set():
                break
            limit.acquire()
            self.lateness_s.append(max(0.0, time.monotonic() - due_t))
            i, item = self._take()
            th = threading.Thread(target=work, args=(k, i, item, due_t), daemon=True,
                                  name=f"perfbench-open-{k}")
            th.start()
            workers.append(th)
        for th in workers:
            th.join(timeout=5)

    # -- lifecycle ---------------------------------------------------------- #
    def start(self, seed: int, horizon_s: float) -> int:
        """Start the mix. Returns how many ``first_done`` releases mean
        "every client has finished one request"."""
        kind = self.traffic["kind"]
        ramp = self.traffic.get("ramp", {})
        expected = float(ramp.get("expected_request_s", 0.0))
        now = time.monotonic()
        if kind in ("closed", "sessions"):
            n = int(self.traffic["clients"])
            loop = self._closed_loop if kind == "closed" else self._session_loop
            for c in range(n):
                th = threading.Thread(
                    target=loop, args=(c, now + expected * c / n), daemon=True,
                    name=f"perfbench-client-{c}",
                )
                th.start()
                self._threads.append(th)
            return n
        if kind == "poisson":
            due = arrival_times(self.traffic, seed, horizon_s)
            th = threading.Thread(target=self._open_loop, args=(now, due), daemon=True,
                                  name="perfbench-open-dispatch")
            th.start()
            self._threads.append(th)
            return 1
        raise ValueError(f"unknown traffic kind {kind!r}")

    def stop(self) -> None:
        """End the run: cut streams that are still open and join."""
        self._stop.set()
        for conn in list(self._conns.values()):
            try:
                if conn.sock is not None:
                    conn.sock.shutdown(2)
            except OSError:
                pass
        for th in self._threads:
            th.join(timeout=20)


def multipart(filename: str, content: bytes) -> Tuple[bytes, Dict[str, str]]:
    boundary = uuid.uuid4().hex
    body = (
        f"--{boundary}\r\nContent-Disposition: form-data; name=\"file\"; "
        f"filename=\"{filename}\"\r\nContent-Type: text/plain\r\n\r\n"
    ).encode() + content + f"\r\n--{boundary}--\r\n".encode()
    return body, {"Content-Type": f"multipart/form-data; boundary={boundary}"}


def http_call(host: str, port: int, method: str, path: str, body: Any = None,
              headers: Optional[Dict[str, str]] = None, timeout: float = 60.0) -> Tuple[int, bytes]:
    """(status, payload); connection errors come back as status 0."""
    headers = dict(headers or {})
    data = None
    if isinstance(body, bytes):
        data = body
    elif body is not None:
        data = json.dumps(body).encode()
        headers.setdefault("Content-Type", "application/json")
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(method, path, body=data, headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read()
    except (OSError, http.client.HTTPException) as exc:
        return 0, repr(exc).encode()
    finally:
        conn.close()


def ingest_corpus(host: str, port: int, docs: List[Tuple[str, str]], threads: int) -> List[str]:
    """POST every document; returns the failures (empty = all stored)."""
    failures: List[str] = []
    it = iter(docs)
    lock = threading.Lock()

    def work():
        while True:
            with lock:
                doc = next(it, None)
            if doc is None:
                return
            body, headers = multipart(doc[0], doc[1].encode())
            status, payload = http_call(host, port, "POST", "/documents", body, headers, timeout=600)
            if status != 200:
                with lock:
                    failures.append(f"{doc[0]}: HTTP {status} {payload[:120]!r}")

    pool = [threading.Thread(target=work, name=f"perfbench-ingest-{i}") for i in range(max(1, threads))]
    for th in pool:
        th.start()
    for th in pool:
        th.join()
    return failures
