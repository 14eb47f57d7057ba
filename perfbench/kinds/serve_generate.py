"""Traffic kind "serve_generate": token prompts through
`ModelHost.register_sequence` and `ModelHost.generate` (the call behind
the HTTP `:generate` route; no HTTP and no JSON in the window).

One generator for every mix, steered by the traffic file:

* "loop": "closed" sends from `clients` threads, each its next request
  when the last returned; "open" sends from one thread on a schedule of
  `rate_rps` with seeded jitter, whether or not earlier requests
  returned, and times each request from when it was due.
* prompt lengths come from the file's fixed multiset in a fixed cycle
  (`deal`): the seed picks where the cycle starts and makes the token
  ids and the weights; it never draws the amount of work.

After the window the host is closed and the program's state freed; the
configuration's plain reference then runs one full forward pass over a
seeded sample of the requests the window finished, the longest among
them, and the served tokens and logits are compared with it.
"""

import gc
import threading
import time

import numpy as np

from perfbench.harness import log
from perfbench.stats import percentile

NAME = "lm"
WAIT_S = 90.0


class Rec:
    __slots__ = ("i", "prompt", "due", "sent", "done", "req", "error")

    def __init__(self, i, prompt, due):
        self.i, self.prompt, self.due = i, prompt, due
        self.sent = self.done = self.req = self.error = None

    def tokens_out(self):
        return 0 if self.req is None else len(self.req.out_tokens)


def build_model(config, seed):
    from deeplearning4j_tpu.nn.transformer import CausalTransformerLM

    m = config["model"]
    return CausalTransformerLM(
        vocab=m["vocab_size"], d_model=m["n_embd"], n_heads=m["n_head"],
        n_layers=m["n_layer"], d_ff=m["n_inner"],
        max_context=m["n_positions"], page_size=m["page_size"],
        dtype=m["dtype"], seed=seed)


def deal(traffic, n, rng):
    """(prompt lengths [n], arrival jitter in [-1, 1] [n]): one fixed
    cycle of `cycle_blocks` copies of the file's multiset, shuffled and
    given its jitter once by the file's `order_seed`, dealt from an
    offset the seed picks. Every seed then sends the same requests at
    the same spacing behind the same neighbours, from another start, and
    a window of whole cycles holds the same work whatever the seed
    (lengths permuted by the seed spread `ttft_p50_ms` by 9%: PERF.md)."""
    block = [length for length, count in traffic["prompt_multiset"]
             for _ in range(count)]
    fixed = np.random.default_rng(int(traffic["order_seed"]))
    cycle = np.concatenate([fixed.permutation(block) for _ in
                            range(int(traffic["cycle_blocks"]))])
    jitter = fixed.uniform(-1.0, 1.0, len(cycle))
    at = (int(rng.integers(len(cycle))) + np.arange(n)) % len(cycle)
    return cycle[at].tolist(), jitter[at]


class Load:
    """The load of one run: requests, senders, waiters, records."""

    def __init__(self, run, host):
        self.host = host
        self.tr = run.traffic
        self.records = []
        self.lock = threading.Lock()
        self.stop = threading.Event()
        self.threads = []
        self.next_i = 0
        rng = run.rng("traffic")
        n = int(self.tr["max_requests"])
        vocab = run.config["model"]["vocab_size"]
        lengths, self.jitter = deal(self.tr, n, rng)
        self.prompts = [rng.integers(0, vocab, length).astype(np.int32)
                        for length in lengths]

    def send(self, rec):
        rec.sent = time.perf_counter()
        with self.lock:
            self.records.append(rec)
        try:
            rec.req = self.host.generate(
                NAME, rec.prompt,
                max_new_tokens=self.tr["max_new_tokens"], wait=False)
        except Exception as e:       # a refusal is an answer: recorded
            rec.error = e
            rec.done = time.perf_counter()

    def await_(self, rec):
        if rec.req is None:
            return
        try:
            rec.req.wait(WAIT_S)
        except Exception as e:
            rec.error = e
        rec.done = time.perf_counter()

    def take(self):
        with self.lock:
            i = self.next_i
            self.next_i += 1
        if i >= len(self.prompts):
            raise RuntimeError("the traffic file's max_requests ran out")
        return i

    # -- closed loop ---------------------------------------------------
    def client(self):
        while not self.stop.is_set():
            i = self.take()
            rec = Rec(i, self.prompts[i], time.perf_counter())
            self.send(rec)
            self.await_(rec)

    def start_clients(self):
        for _ in range(int(self.tr["clients"])):
            t = threading.Thread(target=self.client, daemon=True)
            t.start()
            self.threads.append(t)

    def finished(self):
        with self.lock:
            return [r for r in self.records
                    if r.done is not None and r.error is None]

    # -- open loop -----------------------------------------------------
    def run_schedule(self, t0, seconds):
        """Send on the schedule from this thread; every request gets a
        waiter of its own, so that its answer is timed when it comes and
        not when an earlier one has. Returns how late the sends ran
        (seconds, worst) and the waiters."""
        rate = float(self.tr["rate_rps"])
        n = int(rate * seconds)
        waiters = []
        late = 0.0
        for k in range(n):
            i = self.take()
            due = t0 + (k + 0.5 + self.tr["jitter"] * self.jitter[i]) / rate
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            rec = Rec(i, self.prompts[i], due)
            self.send(rec)
            late = max(late, rec.sent - due)
            t = threading.Thread(target=self.await_, args=(rec,),
                                 daemon=True)
            t.start()
            waiters.append(t)
        return late, waiters

    def tokens_out(self):
        with self.lock:
            recs = list(self.records)
        return sum(r.tokens_out() for r in recs)


def setup(run):
    from deeplearning4j_tpu.serving import ModelHost, greedy_sampler

    s = run.config["serving"]
    if s["sampler"] != "greedy":
        raise ValueError("the comparison with the reference needs greedy "
                         "tokens")
    seed = run.subseed("model")
    t = time.perf_counter()
    model = build_model(run.config, seed)
    log(f"model built in {time.perf_counter() - t:.1f}s")
    host = ModelHost()
    t = time.perf_counter()
    rep = host.register_sequence(
        NAME, model, slotBuckets=tuple(s["slotBuckets"]),
        numPages=s["numPages"], prefixSharing=s["prefixSharing"],
        queueLimit=s["queueLimit"], sampler=greedy_sampler())
    log(f"registered and warmed in {time.perf_counter() - t:.1f}s: "
        f"{rep['warm']}")
    load = Load(run, host)
    t = time.perf_counter()
    if run.traffic["loop"] == "closed":
        # the same mix until every client has finished one request: the
        # window opens with the slots at mixed phases
        load.start_clients()
        need = int(run.traffic["clients"])
        while len(load.finished()) < need:
            if any(r.error is not None for r in load.records):
                raise RuntimeError(f"warm-up request failed: "
                                   f"{[r.error for r in load.records if r.error]}")
            time.sleep(0.05)
    else:
        for _ in range(int(run.traffic["warmup_requests"])):
            i = load.take()
            rec = Rec(i, load.prompts[i], time.perf_counter())
            load.send(rec)
            load.await_(rec)
            if rec.error is not None:
                raise RuntimeError(f"warm-up request failed: {rec.error}")
    log(f"warm-up traffic took {time.perf_counter() - t:.1f}s")
    return {"host": host, "model": model, "load": load, "seed": seed}


def _pages_sampler(run, stop):
    from deeplearning4j_tpu.runtime import telemetry

    fam = telemetry.get_registry().get("dl4j_kv_pages_in_use")
    peak = 0
    while not stop.wait(0.02):
        for child in fam.children():
            peak = max(peak, int(child.value))
    run.counters["kv_pages_in_use_peak"] = peak


def window(run, state, go):
    from deeplearning4j_tpu.runtime import telemetry

    load, host = state["load"], state["host"]
    telemetry.get_registry().trace.clear()
    with load.lock:         # answered before the window: not its work
        warm = set(id(r) for r in load.records if r.done is not None)
    stop_sampler = threading.Event()
    sampler = None
    if run.trace:
        sampler = threading.Thread(target=_pages_sampler,
                                   args=(run, stop_sampler), daemon=True)
        sampler.start()
    t0 = time.perf_counter()
    n0 = load.tokens_out()
    go()
    if run.traffic["loop"] == "closed":
        time.sleep(max(0.0, t0 + run.seconds - time.perf_counter()))
        t1 = time.perf_counter()
        n1 = load.tokens_out()
    else:
        late, waiters = load.run_schedule(t0, run.seconds)
        time.sleep(max(0.0, t0 + run.seconds - time.perf_counter()))
        t1 = time.perf_counter()
        n1 = load.tokens_out()
        for t in waiters:           # every answer due in the window is
            t.join(WAIT_S + 5)      # waited for: late is late, not wrong
        log(f"the generator ran at most {1e3 * late:.2f} ms late")
    stop_sampler.set()
    if sampler is not None:
        sampler.join(5)
    # the scheduler's own series go when it closes: read them first
    fam = telemetry.get_registry().get("dl4j_seq_queue_wait_seconds")
    waits = [c.percentile(50) for c in (fam.children() if fam else [])
             if c.count]
    if waits:
        run.counters["queue_wait_p50_s"] = max(waits)
    load.stop.set()
    host.close(drain=False)
    for t in load.threads:
        t.join(10)
    wall = t1 - t0
    with load.lock:
        recs = [r for r in load.records if id(r) not in warm]
    if run.traffic["loop"] == "closed":
        due = [r for r in recs if r.done is not None and r.done <= t1]
        failed = [r for r in due if r.error is not None]
    else:
        due = recs
        failed = [r for r in due if r.error is not None or r.done is None]
    done = [r for r in due if r.error is None and r.done is not None]
    metrics = {"output_tokens_per_s": (n1 - n0) / wall}
    if run.traffic["loop"] == "open":
        worst = 1e3 * WAIT_S
        ttft = [1e3 * (r.done - r.due) if r in done else worst
                for r in due]
        metrics = {"ttft_p50_ms": percentile(ttft, 50),
                   "ttft_p95_ms": percentile(ttft, 95)}
    log(f"window {wall:.3f}s: {len(due)} requests due, {len(done)} "
        f"finished, {len(failed)} failed, {n1 - n0} tokens emitted; "
        f"errors {[repr(r.error) for r in failed[:3]]}")
    return {"t0": t0, "t1": t1, "wall_s": wall, "attempted": len(due),
            "failed": len(failed), "tokens": n1 - n0, "done": done,
            "metrics": metrics}


def sample_requests(run, done):
    """A seeded sample of the finished requests, the longest in it."""
    k = min(int(run.traffic["check_requests"]), len(done))
    if k == 0:
        return []
    longest = max(range(len(done)), key=lambda j: (
        len(done[j].prompt) + len(done[j].req.out_tokens), -done[j].i))
    order = [j for j in run.rng("check").permutation(len(done)).tolist()
             if j != longest]
    return [done[j] for j in [longest] + order[:k - 1]]


def compare_one(ref_logits, tokens, logits):
    """For one request: the widest gap by which a served token's logit
    lies below the reference's best, and the largest difference between
    a served logits row and the reference's over that row's spread."""
    ref = np.asarray(ref_logits, np.float32)
    idx = np.arange(len(tokens))
    gap = ref.max(axis=1) - ref[idx, np.asarray(tokens)]
    err = np.abs(np.asarray(logits, np.float32) - ref).max(axis=1) \
        / ref.std(axis=1)
    return float(gap.max()), float(err.max())


def reference_logits(run, weights, rec, precision="float32"):
    m = run.config["model"]
    return run.reference.served_logits(
        weights, rec.prompt, rec.req.out_tokens, n_head=m["n_head"],
        pad_to=m["page_size"], precision=precision)


def free_program(state):
    for k in ("host", "model", "load"):
        state.pop(k, None)
    gc.collect()


def compare_sample(run, weights, sample, control=None):
    """The cell's numbers over a sample of finished requests. With
    `control` (a precision of the reference) the control stands in the
    program's place: at each position of the same prompts and tokens it
    serves the token it puts first, with its own logits."""
    gaps, errs = [], []
    for rec in sample:
        ref = np.asarray(reference_logits(run, weights, rec))
        if control is None:
            tokens, logits = rec.req.out_tokens, rec.req.logits
        else:
            logits = np.asarray(reference_logits(run, weights, rec, control))
            tokens = logits.argmax(axis=1)
        g, e = compare_one(ref, tokens, logits)
        gaps.append(g)
        errs.append(e)
    return {"token_gap_max": max(gaps), "logit_err_max": max(errs)}


def check(run, state):
    sample = sample_requests(run, run.window.pop("done"))
    free_program(state)
    limits = run.config["correct"]
    if not sample:
        return [(name, None, lim) for name, lim in limits.items()]
    t = time.perf_counter()
    weights = run.reference.make_weights(state["seed"], run.config["model"])
    log(f"reference weights made in {time.perf_counter() - t:.1f}s")
    got = compare_sample(run, weights, sample)
    log(f"compared {len(sample)} requests, "
        f"{sum(len(r.req.out_tokens) for r in sample)} served tokens, "
        f"longest {len(sample[0].prompt)}+{len(sample[0].req.out_tokens)}")
    state["sample"], state["weights"] = sample, weights
    return [(name, got[name], lim) for name, lim in limits.items()]
