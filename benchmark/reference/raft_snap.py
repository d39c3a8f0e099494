"""Plain reference of the raft model with log compaction and command
rounds (MIT 6.824 Lab 2D): one seed, Python integers and lists.

It restates, on top of ``raft.py``'s model, what the device model adds
when ``snapshot_every`` and ``rounds`` are set:

- Each log entry carries a command value. A node's log is a window over
  absolute indices, ``snap_idx + 1 .. log_len``, index ``i`` in slot
  ``i % log_cap`` of a ring; below the window lies the snapshot
  (``snap_idx``, ``snap_term``, ``snap_digest``), which survives a crash.
- The state machine applies each entry when the node commits it: the
  digest through ``i`` is the sum, modulo 2**32, of ``mix(j, cmd_j)``
  over ``j <= i``. The node snapshots at the last multiple of
  ``snapshot_every`` it committed. A crashed node restarts with its
  commit index and digest at its snapshot.
- A leader sends InstallSnapshot (``M_SNAP``: index, term, digest) to a
  follower whose next index is at or below its snapshot. A follower of
  that term installs a snapshot past its commit index, keeping the
  suffix of its log after a matching ``(index, term)``, and replies as
  to an append, with the snapshot's index. An append whose previous
  index lies below the follower's snapshot is answered with success and
  the snapshot's index.
- A follower commits up to the leader's commit, but not past the last
  entry the append gave it (Raft's Figure 2). A rejected append's reply
  carries ``min(prev index, follower's log length)``, and the leader
  backs its next index up to one past it at once (Lab 2C's XLen). A
  reply that moves a follower's next index (a success that raises its
  match index, or a rejection) is answered at once, in the reply's
  slot, with what the follower needs next, while its next index is at
  or below the leader's log length.
- Round ``r`` (event ``K_ROUND`` at ``r`` virtual seconds) crashes a
  drawn node, restarts it after a drawn down-time, and starts two
  command chains: ``1 + burst`` commands now, ``burst`` drawn from
  ``[snapshot_every / 2, snapshot_every / 2 + snapshot_every)``, and one
  after the restart. A chain sends its next command 1 ms after a leader
  accepts the last one or it reaches the retry cap.

Three checks latch per seed: the two of ``raft.py`` (the log-matching
record kept in a ring of ``log_cap`` indices) and state-machine safety,
the first digest recorded at each snapshot point against every later
one (a ring of ``log_cap`` points).
"""

from __future__ import annotations

from . import raft
from .engine import F_CRASH, F_RESTART, M32, bounded, pay
from .raft import (
    CANDIDATE, FOLLOWER, K_CMD, K_ELECTION, K_FAULT, K_HEARTBEAT, K_MSG,
    LEADER, M_APPEND, M_APPEND_RSP, M_REQ_VOTE, M_VOTE_GRANT, V_COMMIT,
)

K_ROUND = 5
M_SNAP = 4
V_APPLY = 4
SLOTS = 9
ROUND_NS = 1_000_000_000
CMD_GAP_NS = 1_000_000

# what the harness compares, per seed, with the device sweep
FIELDS = raft.FIELDS + (
    "wstate.snap.log_cmd", "wstate.snap.snap_idx", "wstate.snap.snap_term",
    "wstate.snap.snap_digest", "wstate.snap.digest", "wstate.snap.snapshots",
    "wstate.snap.installs_sent", "wstate.snap.installs",
)


def s32(u: int) -> int:
    u &= M32
    return u - (1 << 32) if u >= 1 << 31 else u


def mix(idx: int, cmd: int) -> int:
    """The state machine's step for command ``cmd`` at log index ``idx``."""
    x = ((idx * 0x9E3779B1) & M32) ^ (cmd & M32)
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & M32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & M32
    return x ^ (x >> 16)


class Model(raft.Model):
    payload_slots = SLOTS

    def __init__(self, c: dict):
        super().__init__(c)
        n, L = self.n, self.L
        self.k = c["snapshot_every"]
        if not 0 < self.k < L:
            raise ValueError("the snapshot reference needs 0 < snapshot_every < log_cap")
        self.log_cmd = [[0] * L for _ in range(n)]
        self.snap_idx, self.snap_term = [0] * n, [0] * n
        self.snap_digest, self.digest = [0] * n, [0] * n
        self.c_idx = [0] * L  # index each log-matching slot records
        self.s_idx, self.s_val = [0] * L, [0] * L  # snapshot-point digests
        self.snapshots = self.installs_sent = self.installs = 0

    # -- helpers ------------------------------------------------------------

    def _term_at(self, node, idx):
        if idx == self.snap_idx[node]:
            return self.snap_term[node]
        return self.log_term[node][idx % self.L]

    def _broadcast(self, now, src, rand, enable, pays):
        if enable:
            self.installs_sent += sum(p[1] == M_SNAP for i, p in enumerate(pays)
                                      if i != src)
        return super()._broadcast(now, src, rand, enable, pays)

    def _append_pays(self, leader, term):
        L, snap = self.L, self.snap_idx[leader]
        pays = []
        for i in range(self.n):
            nxt = self.next_idx[leader][i]
            if nxt <= snap:
                pays.append(pay(SLOTS, i, M_SNAP, leader, term, snap,
                                self.snap_term[leader], self.snap_digest[leader]))
                continue
            has = nxt <= self.log_len[leader]
            ent = self.log_term[leader][nxt % L] if has else 0
            cmd = self.log_cmd[leader][nxt % L] if has else 0
            pays.append(pay(SLOTS, i, M_APPEND, leader, term, nxt - 1,
                            self._term_at(leader, nxt - 1), ent,
                            self.commit[leader], cmd))
        return pays

    def _check_point(self, point, digest):
        s = point // self.k % self.L
        if self.s_idx[s] != point:
            self.s_idx[s], self.s_val[s] = point, digest
        elif self.s_val[s] != digest:
            self.violation = True
            self.viol_kind |= V_APPLY

    def _advance_commit(self, node, new_commit):
        L, k = self.L, self.k
        old = self.commit[node]
        new = max(old, new_commit)
        point = new // k * k
        digest = at_point = self.digest[node]
        for i in range(old + 1, new + 1):
            s = i % L
            mine = self.log_term[node][s]
            held = self.c_set[s] and self.c_idx[s] == i
            if held and self.c_term[s] != mine:
                self.violation = True
                self.viol_kind |= V_COMMIT
            if not held:
                self.c_term[s], self.c_set[s], self.c_idx[s] = mine, True, i
            digest = (digest + mix(i, self.log_cmd[node][s])) & M32
            if i == point:
                at_point = digest
        self.commit[node] = new
        self.commits += new - old
        self.digest[node] = digest
        if point > self.snap_idx[node]:
            self.snap_idx[node], self.snap_term[node] = point, self.log_term[node][point % L]
            self.snap_digest[node] = at_point
            self.snapshots += 1
            self._check_point(point, at_point)

    def _install(self, node, idx, term, digest):
        commit = self.commit[node]
        if idx <= commit:
            return
        keep = idx <= self.log_len[node] and self._term_at(node, idx) == term
        digest &= M32
        self.snap_idx[node], self.snap_term[node] = idx, term
        self.snap_digest[node] = self.digest[node] = digest
        self.installs += 1
        self._check_point(idx, digest)
        if not keep:
            self.log_len[node] = idx
        self.commit[node] = idx
        self.commits += idx - commit

    # -- handlers -----------------------------------------------------------

    def init(self, key):
        emits = super().init(key)
        if self.c["rounds"]:
            emits.append((ROUND_NS, K_ROUND, pay(SLOTS, 1), True))
        return emits

    def handle(self, now, kind, p, rand):
        if kind == K_ROUND:
            return self._round(now, p, rand), None
        return super().handle(now, kind, p, rand)

    def _election(self, now, p, rand):
        node, gen = p[0], p[1]
        valid = (self.faults.alive[node] and gen == self.tgen[node]
                 and self.role[node] != LEADER)
        starting = valid and not (self.last_hb[node] + self.c["election_lo_ns"] > now)
        new_term = self.term[node] + 1
        if starting:
            self.term[node], self.role[node] = new_term, CANDIDATE
            self.voted[node], self.votes[node] = node, 1 << node
            self.last_hb[node] = now
        last = self.log_len[node]
        pays = [pay(SLOTS, i, M_REQ_VOTE, node, new_term, last,
                    self._term_at(node, last)) for i in range(self.n)]
        bc = self._broadcast(now, node, rand, starting, pays)
        t = now + self._timeout(rand[2 * self.n])
        return bc + [(t, K_ELECTION, pay(SLOTS, node, self.tgen[node]), valid),
                     self._OFF]

    def _msg(self, now, p, rand):
        n, L = self.n, self.L
        dst, mtype, src, mterm, a, b, cc, d = p[:8]
        if not self.faults.alive[dst]:
            return self._no_bcast() + [self._OFF, self._OFF]
        was_leader = self.role[dst] == LEADER
        if mterm > self.term[dst]:  # term catch-up demotes
            self.term[dst], self.role[dst], self.voted[dst] = mterm, FOLLOWER, -1
        term = self.term[dst]
        my_len, my_snap = self.log_len[dst], self.snap_idx[dst]
        grant = won = signal = consistent = store = below = installing = False
        slot = a + 1
        if mtype == M_REQ_VOTE:
            mine = self._term_at(dst, my_len)
            log_ok = b > mine or (b == mine and a >= my_len)
            grant = (mterm == term and self.voted[dst] in (-1, src) and log_ok)
            if grant:
                self.voted[dst] = src
        elif mtype == M_VOTE_GRANT:
            if self.role[dst] == CANDIDATE and mterm == term:
                self.votes[dst] |= 1 << src
                won = bin(self.votes[dst]).count("1") >= n // 2 + 1
                if won:
                    self.role[dst] = LEADER
        elif mtype in (M_APPEND, M_SNAP) and mterm == term:
            signal = True
            if self.role[dst] == CANDIDATE:
                self.role[dst] = FOLLOWER
            installing = mtype == M_SNAP
            below = mtype == M_APPEND and a < my_snap
            if mtype == M_APPEND and not below:
                consistent = a <= my_len and self._term_at(dst, a) == b
                fits = slot - my_snap <= L
                store = consistent and cc > 0 and fits
                if consistent and cc > 0 and not fits:
                    self.log_overflow = True
                if store:
                    same = slot <= my_len and self._term_at(dst, slot) == cc
                    self.log_term[dst][slot % L] = cc
                    self.log_cmd[dst][slot % L] = p[8]
                    if not same:
                        self.log_len[dst] = slot
        if won:
            self.lepoch[dst] += 1
        if signal or grant or won:
            self.last_hb[dst] = now
        if won:
            self._record_election(term, dst)
        if consistent:
            self._advance_commit(dst, min(d, slot if store else a))
        if installing:
            self._install(dst, a, b, cc)
        pipe = False
        if mtype == M_APPEND_RSP and mterm == term and self.role[dst] == LEADER:
            m_old, nx_old = self.match_idx[dst][src], self.next_idx[dst][src]
            if a == 1:
                m_new = max(m_old, b)
                self.match_idx[dst][src], self.next_idx[dst][src] = m_new, m_new + 1
                moved = m_new > m_old
            else:  # back a short follower up to its length (Lab 2C's XLen)
                self.next_idx[dst][src] = max(min(nx_old - 1, b + 1), 1)
                moved = self.next_idx[dst][src] != nx_old
            pipe = moved and self.next_idx[dst][src] <= self.log_len[dst]
            row, snap = self.match_idx[dst], self.snap_idx[dst]
            best = 0
            for i in range(snap + 1, snap + L + 1):
                reps = 1 + sum(row[j] >= i for j in range(n) if j != dst)
                if (self.commit[dst] < i <= self.log_len[dst] and reps >= n // 2 + 1
                        and self.log_term[dst][i % L] == term):
                    best = i
            if best > 0:
                self._advance_commit(dst, best)
        demoted = was_leader and self.role[dst] != LEADER
        if demoted:
            self.tgen[dst] += 1
        if won:
            self.next_idx[dst] = [self.log_len[dst] + 1] * n
            self.match_idx[dst] = [0] * n
        out = self._append_pays(dst, term)
        bc = self._broadcast(now, dst, rand, won, out)
        rt, rok = self.net.route(now, dst, src, rand[2 * n], rand[2 * n + 1])
        if grant:
            reply = pay(SLOTS, src, M_VOTE_GRANT, dst, mterm)
        elif pipe:  # the follower's next entry, in the reply's slot
            reply = out[src]
            self.installs_sent += int(reply[1] == M_SNAP)
        else:
            match = slot if store else min(a, self.log_len[dst])
            if installing:
                match = a
            elif below:
                match = my_snap
            ok = consistent or below or installing
            reply = pay(SLOTS, src, M_APPEND_RSP, dst, term, int(ok), match)
        attempt = grant or mtype in (M_APPEND, M_SNAP) or pipe
        if won:
            extra = (now + self.c["heartbeat_ns"], K_HEARTBEAT,
                     pay(SLOTS, dst, self.lepoch[dst]), True)
        else:
            extra = (rt, K_MSG, reply, attempt and rok)
        retime = (now + self._timeout(rand[2 * n + 2]), K_ELECTION,
                  pay(SLOTS, dst, self.tgen[dst]), demoted)
        self.sent += int(attempt)
        self.delivered += int(attempt and rok)
        return bc + [extra, retime]

    def _fault(self, now, p, rand):
        v = p[1]
        crashed, restarted = self.faults.apply(p[0], v)
        if crashed or restarted:
            self.role[v] = FOLLOWER
        if crashed:  # restart from the snapshot
            self.votes[v] = 0
            self.commit[v], self.digest[v] = self.snap_idx[v], self.snap_digest[v]
            self.tgen[v] += 1
            self.lepoch[v] += 1
        if restarted:
            self.last_hb[v] = now
        t = now + self._timeout(rand[0])
        return self._no_bcast() + [
            (t, K_ELECTION, pay(SLOTS, v, self.tgen[v]), restarted), self._OFF]

    def _cmd(self, now, p, rand):
        target, retries, more = p[0], p[1], p[2]
        leader = self.faults.alive[target] and self.role[target] == LEADER
        slot = self.log_len[target] + 1
        accept = leader and slot - self.snap_idx[target] <= self.L
        if accept:
            s = slot % self.L
            self.log_term[target][s] = self.term[target]
            self.log_cmd[target][s] = s32(rand[0])
            self.log_len[target] = slot
            self.accepted += 1
        elif leader:
            self.log_overflow = True
        give_up = not accept and retries + 1 >= self.c["cmd_max_retries"]
        self.giveups += int(give_up)
        nxt = (target + 1) % self.n
        if accept or give_up:
            ev = (now + CMD_GAP_NS, K_CMD,
                  pay(SLOTS, target if accept else nxt, 0, more - 1), more > 0)
        else:
            ev = (now + self.c["cmd_retry_ns"], K_CMD,
                  pay(SLOTS, nxt, retries + 1, more), True)
        return self._no_bcast() + [ev, self._OFF]

    def _round(self, now, p, rand):
        r, n, c = p[0], self.n, self.c
        victim = bounded(rand[0], 0, n)
        up = now + bounded(rand[1], c["restart_lo_ns"], c["restart_hi_ns"])
        half = self.k // 2
        burst = bounded(rand[2], half, half + self.k)

        def fault(action, t):
            return (t, K_FAULT, pay(SLOTS, action, victim, t & 0x7FFFFFFF, t >> 31),
                    True)

        def cmd(t, u, more):
            return (t, K_CMD, pay(SLOTS, bounded(u, 0, n), 0, more), True)

        out = [fault(F_CRASH, now), fault(F_RESTART, up),
               cmd(now + CMD_GAP_NS, rand[3], burst),
               cmd(up + CMD_GAP_NS, rand[4], 0),
               ((r + 1) * ROUND_NS, K_ROUND, pay(SLOTS, r + 1), r < c["rounds"])]
        return out + [self._OFF] * (n + 2 - len(out))

    def fields(self) -> dict:
        out = super().fields()
        out.update({
            "wstate.snap.log_cmd": self.log_cmd,
            "wstate.snap.snap_idx": self.snap_idx,
            "wstate.snap.snap_term": self.snap_term,
            "wstate.snap.snap_digest": self.snap_digest,
            "wstate.snap.digest": self.digest,
            "wstate.snap.snapshots": self.snapshots,
            "wstate.snap.installs_sent": self.installs_sent,
            "wstate.snap.installs": self.installs,
        })
        return out
