"""Plain reference of the seeded event loop, one seed at a time.

This is the yardstick the benchmark's correctness check compares the
device sweep against. It imports nothing of ``madsim_tpu``: it restates,
in plain Python integers and lists, the semantics the engine documents,
so that the same seed gives the same events, clock and state.

- Randomness is counter based: the seed's key is ``(hi32, lo32)`` of the
  seed, draw block ``i`` of event ``c`` is Threefry-2x32 of the key
  folded with ``c``, taken at counter ``(0, i)``, lanes xor-ed.
- The queue is a table of ``capacity`` slots. A push of ``E`` emits gives
  emit ``e`` the ``e``-th free slot in ascending order, written only when
  the emit is enabled. A pop takes the earliest time; equal times are
  broken by the smallest hash of the slot index mixed with one draw.
- One step pops the earliest event, moves the clock to it plus a jitter
  of 50 to 100 ns, and dispatches it to the model unless the clock passed
  the time limit or the queue was empty, either of which ends the seed.
"""

from __future__ import annotations

M32 = 0xFFFFFFFF
INVALID_TIME = (1 << 63) - 1
_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
FAULT_STREAM = 0x5EEDFA17 & 0x7FFFFFFF
INIT_STREAM = 0x7FFFFFFF

# fault actions (the device schedule's wire codes)
F_CRASH, F_RESTART, F_PART, F_HEAL = 0, 1, 2, 3


def threefry2x32(k0: int, k1: int, x0: int, x1: int):
    """Threefry-2x32 with 20 rounds (Salmon et al., SC 2011)."""
    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0 = (x0 + ks[0]) & M32
    x1 = (x1 + ks[1]) & M32
    for i in range(5):
        for j in range(4):
            r = _ROT[(i % 2) * 4 + j]
            x0 = (x0 + x1) & M32
            x1 = (((x1 << r) | (x1 >> (32 - r))) & M32) ^ x0
        x0 = (x0 + ks[(i + 1) % 3]) & M32
        x1 = (x1 + ks[(i + 2) % 3] + i + 1) & M32
    return x0, x1


def seed_key(seed: int):
    s = seed & ((1 << 64) - 1)
    return (s >> 32) & M32, s & M32


def fold_in(key, data: int):
    return threefry2x32(key[0], key[1], 0, data & M32)


def bits(key, n: int) -> list:
    out = []
    for i in range(n):
        a, b = threefry2x32(key[0], key[1], 0, i)
        out.append(a ^ b)
    return out


def bounded(u: int, low: int, high: int) -> int:
    """A uint32 draw mapped to ``[low, high)`` by multiply-shift."""
    span = high - low
    carry = ((u & 0xFFFF) * span) >> 16
    return low + (((u >> 16) * span + carry) >> 16)


def coin(u: int, prob_q32: int) -> bool:
    return u < prob_q32


def _slot_prio(slot: int, tie: int) -> int:
    x = ((slot * 2654435761) & M32) ^ tie
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & M32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & M32
    return x ^ (x >> 16)


class Queue:
    def __init__(self, capacity: int, payload_slots: int):
        self.time = [INVALID_TIME] * capacity
        self.kind = [0] * capacity
        self.pay = [[0] * payload_slots for _ in range(capacity)]

    def size(self) -> int:
        return sum(t != INVALID_TIME for t in self.time)

    def push(self, emits) -> bool:
        """``emits``: (time, kind, pay, enable) tuples; True on overflow."""
        free = [i for i, t in enumerate(self.time) if t == INVALID_TIME]
        overflow = False
        for e, (t, kind, pay, on) in enumerate(emits):
            if not on:
                continue
            if e >= len(free):
                overflow = True
                continue
            s = free[e]
            self.time[s], self.kind[s], self.pay[s] = t, kind, list(pay)
        return overflow

    def pop(self, tie: int):
        t = min(self.time)
        if t == INVALID_TIME:
            return None
        cands = [i for i, x in enumerate(self.time) if x == t]
        slot = min(cands, key=lambda i: (_slot_prio(i, tie), i))
        self.time[slot] = INVALID_TIME
        return t, self.kind[slot], list(self.pay[slot])


def pay(slots: int, *vals) -> list:
    return [int(v) for v in vals] + [0] * (slots - len(vals))


def fault_schedule(key, spec: dict, num_nodes: int, kind: int, slots: int):
    """The seed's fault events as emits, in pair order. ``spec`` holds the
    crash and partition categories of the configuration (counts, windows,
    down-time ranges, victim ranges)."""
    cats = (
        ("crashes", F_CRASH, F_RESTART, "crash_window_ns", "restart_lo_ns",
         "restart_hi_ns", "crash_group"),
        ("partitions", F_PART, F_HEAL, "part_window_ns", "part_lo_ns",
         "part_hi_ns", "part_group"),
    )
    pairs = sum(spec.get(c[0], 0) for c in cats)
    rand = bits(fold_in(key, FAULT_STREAM), 3 * pairs)
    out, i = [], 0
    for count, on, off, window, lo, hi, group in cats:
        vlo, vhi = spec.get(group, (0, -1))
        vhi = num_nodes if vhi < 0 else vhi
        for _ in range(spec.get(count, 0)):
            t0 = bounded(rand[3 * i], 0, spec[window])
            dur = bounded(rand[3 * i + 1], spec[lo], spec[hi])
            vic = bounded(rand[3 * i + 2], vlo, vhi)
            for t, action in ((t0, on), (t0 + dur, off)):
                out.append((t, kind, pay(slots, action, vic, t & 0x7FFFFFFF,
                                         t >> 31), True))
            i += 1
    return out


class Faults:
    """Liveness and partition state of the nodes under the schedule."""

    def __init__(self, n: int):
        self.alive = [True] * n
        self.part_in = [0] * n
        self.part_out = [0] * n
        self.clog = [[False] * n for _ in range(n)]

    def apply(self, action: int, v: int):
        """Returns (crashed, restarted): the edges the event caused."""
        crashed = action == F_CRASH and self.alive[v]
        restarted = action == F_RESTART and not self.alive[v]
        if action == F_CRASH:
            self.alive[v] = False
        elif action == F_RESTART:
            self.alive[v] = True
        elif action in (F_PART, F_HEAL):
            d = 1 if action == F_PART else -1
            self.part_in[v] = max(self.part_in[v] + d, 0)
            self.part_out[v] = max(self.part_out[v] + d, 0)
            n = len(self.alive)
            self.clog = [[self.part_out[s] > 0 or self.part_in[t] > 0
                          for t in range(n)] for s in range(n)]
        else:
            raise ValueError(f"fault action {action} is not in the reference")
        return crashed, restarted


class Net:
    """Per-message link test: loss coin, then a uniform latency."""

    def __init__(self, faults: Faults, loss_q32: int, lat_lo: int, lat_hi: int):
        self.faults, self.loss = faults, loss_q32
        self.lat_lo, self.lat_hi = lat_lo, lat_hi

    def route(self, now: int, src: int, dst: int, u_loss: int, u_lat: int):
        lost = coin(u_loss, self.loss) or self.faults.clog[src][dst]
        return now + bounded(u_lat, self.lat_lo, self.lat_hi + 1), not lost


def run_seed(model, seed: int, engine: dict) -> dict:
    """Run one seed to its end. ``model`` is a fresh per-seed object with
    ``init(key) -> emits``, ``handle(now, kind, pay, rand) -> (emits,
    history row or None)``, ``num_rand``, ``payload_slots``,
    ``hist_slots`` and ``fields() -> dict``."""
    key = seed_key(seed)
    q = Queue(engine["queue_capacity"], model.payload_slots)
    overflow = q.push(model.init(key))
    qmax = q.size()
    now = ctr = steps = 0
    hist_rows, hist_t, hist_ov = [], [], False
    slots = model.hist_slots
    while steps < engine["max_steps"]:
        steps += 1
        rand = bits(fold_in(key, ctr), model.num_rand + 2)
        ev = q.pop(rand[1])
        if ev is None:
            break
        t, kind, p = ev
        clock = max(now, t) + bounded(rand[0], engine["jitter_lo_ns"],
                                      engine["jitter_hi_ns"] + 1)
        if clock > engine["time_limit_ns"]:
            qmax = max(qmax, q.size())
            break
        emits, row = model.handle(clock, kind, p, rand[2:])
        overflow |= q.push(emits)
        if row is not None and slots:
            if len(hist_rows) < slots:
                hist_rows.append(row)
                hist_t.append(clock)
            else:
                hist_ov = True
        now, ctr = clock, ctr + 1
        qmax = max(qmax, q.size())
    out = {"seed": seed, "ctr": ctr, "now_ns": now, "overflow": int(overflow),
           "qmax": qmax}
    if slots:
        pad = slots - len(hist_rows)
        out.update(hist_len=len(hist_rows), hist_overflow=int(hist_ov),
                   hist_rec=hist_rows + [[0] * 5] * pad,
                   hist_t=hist_t + [0] * pad)
    out.update(model.fields())
    return out
