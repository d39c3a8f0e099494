"""Plain reference of the raft model: one seed, Python integers and lists.

Raft as Ongaro and Ousterhout (USENIX ATC 2014) give it, with the choices
the device model makes: election timers that carry a generation and are
dropped when stale, a heartbeat chain per leadership epoch, one log entry
per AppendEntries, commit by the current-term majority rule, client
commands that walk the nodes until a leader takes them, and crashes that
keep term, vote and log but lose role, votes and commit index.

Two safety checks latch per seed: at most one leader per term (over a
ring of the last ``history`` wins), and every node that commits index
``i`` holds the term the first committer held there.
"""

from __future__ import annotations

from .engine import Faults, Net, bounded, bits, fault_schedule, fold_in, pay
from .engine import INIT_STREAM

K_ELECTION, K_HEARTBEAT, K_MSG, K_FAULT, K_CMD = range(5)
M_REQ_VOTE, M_VOTE_GRANT, M_APPEND, M_APPEND_RSP = range(4)
FOLLOWER, CANDIDATE, LEADER = range(3)
V_ELECTION, V_COMMIT = 1, 2
SLOTS = 8

# what the harness compares, per seed, with the device sweep
FIELDS = (
    "ctr", "now_ns", "overflow", "qmax",
    "wstate.role", "wstate.term", "wstate.voted", "wstate.log_term",
    "wstate.log_len", "wstate.commit", "wstate.violation", "wstate.viol_kind",
    "wstate.log_overflow", "wstate.elections", "wstate.commits",
    "wstate.accepted_cmds", "wstate.cmd_giveups", "wstate.msgs_sent",
    "wstate.msgs_delivered",
)


class Model:
    hist_slots = 0
    payload_slots = SLOTS

    def __init__(self, c: dict):
        for k in ("volatile_state", "event_mix"):
            if c[k]:
                raise ValueError(f"the raft reference does not model {k}")
        if c["buggify_q32"] or c["hist_slots"]:
            raise ValueError("the raft reference models no latency spikes "
                             "and records no history")
        self.c = c
        n, L = c["num_nodes"], c["log_cap"]
        self.n, self.L = n, L
        self.num_rand = 2 * n + 3
        self.faults = Faults(n)
        self.net = Net(self.faults, c["loss_q32"], c["lat_lo_ns"], c["lat_hi_ns"])
        self.role = [FOLLOWER] * n
        self.term = [0] * n
        self.voted = [-1] * n
        self.votes = [0] * n
        self.last_hb = [0] * n
        self.tgen = [0] * n
        self.lepoch = [0] * n
        self.log_term = [[0] * L for _ in range(n)]
        self.log_len = [0] * n
        self.commit = [0] * n
        self.next_idx = [[1] * n for _ in range(n)]
        self.match_idx = [[0] * n for _ in range(n)]
        H = c["history"]
        self.h_term, self.h_node, self.h_valid = [0] * H, [0] * H, [False] * H
        self.h_pos = 0
        self.c_term, self.c_set = [0] * L, [False] * L
        self.violation, self.viol_kind, self.log_overflow = False, 0, False
        self.elections = self.commits = self.accepted = self.giveups = 0
        self.sent = self.delivered = 0

    # -- helpers ------------------------------------------------------------

    def _timeout(self, u):
        return bounded(u, self.c["election_lo_ns"], self.c["election_hi_ns"])

    def _term_at(self, node, idx):
        return self.log_term[node][idx] if 0 <= idx < self.L else 0

    def _broadcast(self, now, src, rand, enable, pays):
        out = []
        for i in range(self.n):
            t, ok = self.net.route(now, src, i, rand[2 * i], rand[2 * i + 1])
            out.append((t, K_MSG, pays[i], enable and i != src and ok))
        if enable:
            self.sent += self.n - 1
        self.delivered += sum(e[3] for e in out)
        return out

    def _append_pays(self, leader, term):
        pays = []
        for i in range(self.n):
            nxt = self.next_idx[leader][i]
            has = nxt <= self.log_len[leader]
            ent = self._term_at(leader, min(nxt, self.L - 1)) if has else 0
            pays.append(pay(SLOTS, i, M_APPEND, leader, term, nxt - 1,
                            self._term_at(leader, nxt - 1), ent,
                            self.commit[leader]))
        return pays

    def _record_election(self, term, node):
        dup = any(v and t == term and nd != node
                  for v, t, nd in zip(self.h_valid, self.h_term, self.h_node))
        if dup:
            self.violation = True
            self.viol_kind |= V_ELECTION
        s = self.h_pos % len(self.h_term)
        self.h_term[s], self.h_node[s], self.h_valid[s] = term, node, True
        self.h_pos += 1
        self.elections += 1

    def _advance_commit(self, node, new_commit):
        old = self.commit[node]
        new = max(old, new_commit)
        for i in range(old + 1, min(new, self.L - 1) + 1):
            mine = self.log_term[node][i]
            if self.c_set[i] and self.c_term[i] != mine:
                self.violation = True
                self.viol_kind |= V_COMMIT
            if not self.c_set[i]:
                self.c_term[i], self.c_set[i] = mine, True
        self.commit[node] = new
        self.commits += new - old

    def _no_bcast(self):
        return [(0, K_MSG, [0] * SLOTS, False)] * self.n

    _OFF = (0, 0, [0] * SLOTS, False)

    # -- handlers -----------------------------------------------------------

    def init(self, key):
        n, c = self.n, self.c
        r = bits(fold_in(key, INIT_STREAM), n + 2 * c["commands"])
        emits = [(self._timeout(r[i]), K_ELECTION, pay(SLOTS, i, 0), True)
                 for i in range(n)]
        for k in range(c["commands"]):
            emits.append((bounded(r[n + 2 * k], 0, c["cmd_window_ns"]), K_CMD,
                          pay(SLOTS, bounded(r[n + 2 * k + 1], 0, n), 0), True))
        spec = {k: c[k] for k in ("crashes", "crash_window_ns", "restart_lo_ns",
                                  "restart_hi_ns")}
        return emits + fault_schedule(key, spec, n, K_FAULT, SLOTS)

    def handle(self, now, kind, p, rand):
        fn = (self._election, self._heartbeat, self._msg, self._fault,
              self._cmd)[kind]
        return fn(now, p, rand), None

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
                    self.log_term[node][last]) for i in range(self.n)]
        bc = self._broadcast(now, node, rand, starting, pays)
        t = now + self._timeout(rand[2 * self.n])
        return bc + [(t, K_ELECTION, pay(SLOTS, node, self.tgen[node]), valid),
                     self._OFF]

    def _heartbeat(self, now, p, rand):
        node, epoch = p[0], p[1]
        valid = (self.faults.alive[node] and self.role[node] == LEADER
                 and epoch == self.lepoch[node])
        bc = self._broadcast(now, node, rand, valid,
                             self._append_pays(node, self.term[node]))
        return bc + [(now + self.c["heartbeat_ns"], K_HEARTBEAT,
                      pay(SLOTS, node, epoch), valid), self._OFF]

    def _msg(self, now, p, rand):
        n = self.n
        dst, mtype, src, mterm, a, b, cc, d = p
        if not self.faults.alive[dst]:
            return self._no_bcast() + [self._OFF, self._OFF]
        was_leader = self.role[dst] == LEADER
        if mterm > self.term[dst]:  # term catch-up demotes
            self.term[dst], self.role[dst], self.voted[dst] = mterm, FOLLOWER, -1
        term = self.term[dst]
        my_len = self.log_len[dst]
        grant = won = heard = consistent = store = False
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
        elif mtype == M_APPEND and mterm == term:
            heard = True
            if self.role[dst] == CANDIDATE:
                self.role[dst] = FOLLOWER
            consistent = a <= my_len and self._term_at(dst, a) == b
            store = consistent and cc > 0 and slot < self.L
            if consistent and cc > 0 and slot >= self.L:
                self.log_overflow = True
            if store:
                same = slot <= my_len and self._term_at(dst, min(slot, self.L - 1)) == cc
                self.log_term[dst][slot] = cc
                if not same:
                    self.log_len[dst] = slot
        if won:
            self.lepoch[dst] += 1
        if heard or grant or won:
            self.last_hb[dst] = now
        if won:
            self._record_election(term, dst)
        if consistent:
            self._advance_commit(dst, min(d, self.log_len[dst]))
        if mtype == M_APPEND_RSP and mterm == term and self.role[dst] == LEADER:
            m_old, nx_old = self.match_idx[dst][src], self.next_idx[dst][src]
            if a == 1:
                m_new = max(m_old, b)
                self.match_idx[dst][src], self.next_idx[dst][src] = m_new, m_new + 1
            else:
                self.next_idx[dst][src] = max(nx_old - 1, 1)
            row = self.match_idx[dst]
            best = 0
            for i in range(self.L):
                reps = 1 + sum(row[j] >= i for j in range(n) if j != dst)
                if (self.commit[dst] < i <= self.log_len[dst] and reps >= n // 2 + 1
                        and self.log_term[dst][i] == term):
                    best = i
            if best > 0:
                self._advance_commit(dst, best)
        demoted = was_leader and self.role[dst] != LEADER
        if demoted:
            self.tgen[dst] += 1
        if won:
            self.next_idx[dst] = [self.log_len[dst] + 1] * n
            self.match_idx[dst] = [0] * n
        bc = self._broadcast(now, dst, rand, won, self._append_pays(dst, term))
        rt, rok = self.net.route(now, dst, src, rand[2 * n], rand[2 * n + 1])
        if grant:
            reply = pay(SLOTS, src, M_VOTE_GRANT, dst, mterm)
        else:
            match = slot if store else min(a, self.log_len[dst])
            reply = pay(SLOTS, src, M_APPEND_RSP, dst, term, int(consistent), match)
        attempt = grant or mtype == M_APPEND
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
        if crashed:
            self.votes[v] = self.commit[v] = 0
            self.tgen[v] += 1
            self.lepoch[v] += 1
        if restarted:
            self.last_hb[v] = now
        t = now + self._timeout(rand[0])
        return self._no_bcast() + [
            (t, K_ELECTION, pay(SLOTS, v, self.tgen[v]), restarted), self._OFF]

    def _cmd(self, now, p, rand):
        target, retries = p[0], p[1]
        leader = self.faults.alive[target] and self.role[target] == LEADER
        slot = self.log_len[target] + 1
        accept = leader and slot < self.L
        if accept:
            self.log_term[target][slot] = self.term[target]
            self.log_len[target] = slot
            self.accepted += 1
        elif leader:
            self.log_overflow = True
        give_up = not accept and retries + 1 >= self.c["cmd_max_retries"]
        self.giveups += int(give_up)
        return self._no_bcast() + [
            (now + self.c["cmd_retry_ns"], K_CMD,
             pay(SLOTS, (target + 1) % self.n, retries + 1),
             not accept and not give_up), self._OFF]

    def fields(self) -> dict:
        return {
            "wstate.role": self.role, "wstate.term": self.term,
            "wstate.voted": self.voted, "wstate.log_term": self.log_term,
            "wstate.log_len": self.log_len, "wstate.commit": self.commit,
            "wstate.violation": int(self.violation),
            "wstate.viol_kind": self.viol_kind,
            "wstate.log_overflow": int(self.log_overflow),
            "wstate.elections": self.elections, "wstate.commits": self.commits,
            "wstate.accepted_cmds": self.accepted,
            "wstate.cmd_giveups": self.giveups,
            "wstate.msgs_sent": self.sent, "wstate.msgs_delivered": self.delivered,
        }
