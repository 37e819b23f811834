// The plain reference for the benchmark's `correct` decision on the ABD
// linearizable register (stateright examples/linearizable-register.rs,
// the reference's `linearizable-register check 2`): a multithreaded
// breadth-first model checker, independent of the JAX program under test.
//
// The model: S ABD servers (ids 0..S-1) and C register clients (ids S..),
// client c Putting 'A'+c with request id S+c to server (S+c) % S, then
// Getting with request id 2(S+c) from server (S+c+1) % S
// (src/actor/register.rs:145-237); an unordered non-duplicating network
// held as an unbounded sorted multiset of envelopes; one Deliver action per
// distinct envelope; a delivery that changes nothing and sends nothing is
// no transition (src/actor/model.rs:345-347). Servers keep their phase-1
// responses per peer and their phase-2 acks as sets and count them against
// a majority of S, for any S.
//
// The history is kept per client as its ops (write or read, the peers'
// completed-op counts at invocation, returned or not, the value read), and
// "linearizable" is decided by a direct backtracking search for a legal
// serialization of it: every completed op placed, in-flight ops placed or
// left out, each op after its client's earlier ops and after every op
// that completed before it was invoked, each completed read returning the
// register's value at its place (src/semantics/linearizability.rs).
//
// Usage (the command line and output of bfs_ref.cpp):
//   bfs_ref_abd bfs abd C S POOL THREADS FP_BITS SALT
//     -> "generated=G unique=U depth=D", then "prop <i> <min depth or 0>"
//        per property, then "level <d> <unique at depth d> <generated by
//        expanding them>" per BFS level, then "max_in_flight <n>"
//   bfs_ref_abd path abd C S POOL < paths
//     stdin: "<n paths>", then per path "<prop index> <n states>" and one
//     line of fields per state; stdout: "<invalid> <n states>" per path.
// Properties: 0 "linearizable" (always), 1 "value chosen" (sometimes: a
// GetOk of a written value in flight), 2 "pool capacity" (always: at most
// POOL envelopes in flight — the program under test holds POOL slots).
// Path fields per state: per server clock id val phase req requester write
// read acks_mask, then per server slot j present clock id val; per client
// phase ret frontier_0..frontier_{C-1}; then per envelope type src dst req
// clock id val. Values: 0 NULL, 1+k 'A'+k, 15 none.

#include <algorithm>
#include <array>
#include <atomic>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <iostream>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_set>
#include <vector>

namespace {

using u32 = uint32_t;
using u64 = uint64_t;

inline u64 mix64(u64 h) {
  h = (h ^ (h >> 30)) * 0xBF58476D1CE4E5B9ull;
  h = (h ^ (h >> 27)) * 0x94D049BB133111EBull;
  return h ^ (h >> 31);
}

inline u64 fingerprint(const std::vector<u32>& lanes, u64 salt, u64 mask) {
  u64 h = 0x5851F42D4C957F2Dull ^ salt ^ mix64(lanes.size());
  for (size_t i = 0; i < lanes.size(); ++i)
    h = mix64(h ^ (u64(lanes[i]) + 0x9E3779B97F4A7C15ull * u64(i + 1)));
  h &= mask;
  return h ? h : 1;
}

enum Kind { ALWAYS, SOMETIMES };

constexpr u32 NONE = 15;  // value code of "no value"
enum Msg { PUT, GET, PUT_OK, GET_OK, QUERY, ACK_QUERY, RECORD, ACK_RECORD };

struct Seq {
  u32 clock = 0, id = 0;
  bool operator<(const Seq& o) const {
    return clock != o.clock ? clock < o.clock : id < o.id;
  }
  bool operator==(const Seq& o) const { return clock == o.clock && id == o.id; }
};

struct Env {
  u32 type = 0, src = 0, dst = 0, req = 0;
  Seq seq;
  u32 val = 0;
  u32 code() const {  // 3 + 4 + 4 + 6 + 4 + 4 + 4 bits
    return type | (src << 3) | (dst << 7) | (req << 11) | (seq.clock << 17) |
           (seq.id << 21) | (val << 25);
  }
  static Env of(u32 c) {
    Env e;
    e.type = c & 7; e.src = (c >> 3) & 15; e.dst = (c >> 7) & 15;
    e.req = (c >> 11) & 63; e.seq.clock = (c >> 17) & 15;
    e.seq.id = (c >> 21) & 15; e.val = (c >> 25) & 15;
    return e;
  }
};

constexpr int MAXS = 7, MAXC = 3;

struct Server {
  Seq seq;
  u32 val = 0;   // 0 NULL, 1+k 'A'+k
  u32 phase = 0; // 0 none, 1 querying, 2 recording
  u32 req = 0, requester = 0, write = NONE, read = NONE;
  u32 acks = 0;            // phase 2: bitmask of servers
  u32 resp_present = 0;    // phase 1: bitmask of servers
  std::array<Seq, MAXS> resp_seq{};
  std::array<u32, MAXS> resp_val{};
};

struct Op {
  bool write = false;
  u32 value = 0;                    // written value
  std::array<u32, MAXC> frontier{}; // peers' completed ops at invocation
  bool returned = false;
  u32 ret = 0;                      // value read
};

// A client's history: its Put, then its Get once invoked.
struct Client {
  u32 n_ops = 0;
  std::array<Op, 2> ops{};
  u32 completed() const {
    u32 n = 0;
    for (u32 j = 0; j < n_ops; ++j) n += ops[j].returned;
    return n;
  }
};

// The whole system, unpacked: what a delivery reads and writes.
struct Sys {
  std::array<Server, MAXS> servers{};
  std::array<Client, MAXC> clients{};
  std::vector<u32> net;  // envelope codes, a multiset
};

struct Abd {
  int C, S, pool_cap;
  size_t head;  // lanes before the envelopes

  // A state is its packed identity: per server 2 + S lanes, per client 3,
  // then the envelope codes in sorted order (any number of them).
  struct State {
    std::vector<u32> lanes;
  };

  Abd(int c, int s, int pool) : C(c), S(s), pool_cap(pool) {
    if (C < 1 || C > MAXC || S < 1 || S > MAXS) {
      std::fprintf(stderr, "abd: 1..%d clients, 1..%d servers\n", MAXC, MAXS);
      std::exit(2);
    }
    head = size_t(S) * (2 + S) + 3 * size_t(C);
  }

  static std::vector<Kind> kinds() { return {ALWAYS, SOMETIMES, ALWAYS}; }

  int majority() const { return S / 2 + 1; }
  u32 put_req(int c) const { return u32(S + c); }
  u32 get_req(int c) const { return u32(2 * (S + c)); }

  static u32 pack_op(const Op& o, int C) {
    u32 f = 0;
    for (int j = 0; j < C; ++j) f |= o.frontier[j] << (4 * j);
    return u32(o.write) | (o.value << 1) | (u32(o.returned) << 5) |
           (o.ret << 6) | (f << 10);
  }
  static Op unpack_op(u32 x, int C) {
    Op o;
    o.write = x & 1; o.value = (x >> 1) & 15; o.returned = (x >> 5) & 1;
    o.ret = (x >> 6) & 15;
    for (int j = 0; j < C; ++j) o.frontier[j] = (x >> (10 + 4 * j)) & 15;
    return o;
  }

  // Packs `sys` (its envelopes sorted first) into `st`.
  void encode(Sys& sys, State& st) const {
    std::sort(sys.net.begin(), sys.net.end());
    auto& L = st.lanes;
    L.clear();
    L.reserve(head + sys.net.size());
    for (int s = 0; s < S; ++s) {
      const Server& sv = sys.servers[s];
      L.push_back(sv.seq.clock | (sv.seq.id << 4) | (sv.val << 8) |
                  (sv.phase << 12) | (sv.req << 14) | (sv.requester << 20) |
                  (sv.write << 24) | (sv.read << 28));
      L.push_back(sv.acks | (sv.resp_present << 8));
      for (int j = 0; j < S; ++j)
        L.push_back((sv.resp_present >> j) & 1
                        ? sv.resp_seq[j].clock | (sv.resp_seq[j].id << 4) |
                              (sv.resp_val[j] << 8)
                        : 0);
    }
    for (int c = 0; c < C; ++c) {
      const Client& cl = sys.clients[c];
      L.push_back(cl.n_ops);
      for (int j = 0; j < 2; ++j)
        L.push_back(u32(j) < cl.n_ops ? pack_op(cl.ops[j], C) : 0);
    }
    L.insert(L.end(), sys.net.begin(), sys.net.end());
  }

  void decode(const u32* lanes, size_t n, Sys& sys) const {
    const u32* L = lanes;
    for (int s = 0; s < S; ++s, L += 2 + S) {
      Server& sv = sys.servers[s];
      sv.seq = {L[0] & 15, (L[0] >> 4) & 15};
      sv.val = (L[0] >> 8) & 15; sv.phase = (L[0] >> 12) & 3;
      sv.req = (L[0] >> 14) & 63; sv.requester = (L[0] >> 20) & 15;
      sv.write = (L[0] >> 24) & 15; sv.read = (L[0] >> 28) & 15;
      sv.acks = L[1] & 255; sv.resp_present = (L[1] >> 8) & 255;
      for (int j = 0; j < S; ++j) {
        sv.resp_seq[j] = {L[2 + j] & 15, (L[2 + j] >> 4) & 15};
        sv.resp_val[j] = (L[2 + j] >> 8) & 15;
      }
    }
    for (int c = 0; c < C; ++c, L += 3) {
      Client& cl = sys.clients[c];
      cl.n_ops = L[0];
      for (int j = 0; j < 2; ++j) cl.ops[j] = unpack_op(L[1 + j], C);
    }
    sys.net.assign(lanes + head, lanes + n);
  }

  void send(Sys& sys, u32 type, u32 src, u32 dst, u32 req, Seq seq = {},
            u32 val = 0) const {
    Env e; e.type = type; e.src = src; e.dst = dst; e.req = req;
    e.seq = seq; e.val = val;
    sys.net.push_back(e.code());
  }

  void invoke(Sys& sys, int c, bool write, u32 value) const {
    Op op; op.write = write; op.value = value;
    for (int j = 0; j < C; ++j)
      op.frontier[j] = j == c ? 0 : sys.clients[j].completed();
    sys.clients[c].ops[sys.clients[c].n_ops++] = op;
  }

  std::vector<State> init_states() const {
    Sys sys;
    for (int s = 0; s < S; ++s) sys.servers[s].seq = {0, u32(s)};
    for (int c = 0; c < C; ++c) {
      invoke(sys, c, true, u32(c + 1));
      send(sys, PUT, S + c, (S + c) % S, put_req(c), {}, u32(c + 1));
    }
    State st;
    encode(sys, st);
    return {st};
  }

  // Delivers `e` into `sys` (the envelope already removed). False where
  // the delivery changes nothing and sends nothing.
  bool deliver(Sys& sys, const Env& e) const {
    if (int(e.dst) >= S) {  // a client
      int c = int(e.dst) - S;
      Client& cl = sys.clients[c];
      bool awaiting_put = cl.n_ops == 1 && !cl.ops[0].returned;
      bool awaiting_get = cl.n_ops == 2 && !cl.ops[1].returned;
      if (e.type == PUT_OK && awaiting_put && e.req == put_req(c)) {
        cl.ops[0].returned = true;
        invoke(sys, c, false, 0);
        send(sys, GET, S + c, (S + c + 1) % S, get_req(c));
        return true;
      }
      if (e.type == GET_OK && awaiting_get && e.req == get_req(c)) {
        cl.ops[1].returned = true;
        cl.ops[1].ret = e.val;
        return true;
      }
      return false;
    }
    const u32 self = e.dst;
    Server& sv = sys.servers[self];
    auto broadcast = [&](u32 type, u32 req, Seq seq, u32 val) {
      for (int p = 0; p < S; ++p)
        if (u32(p) != self) send(sys, type, self, p, req, seq, val);
    };
    switch (e.type) {
      case PUT:
      case GET:
        if (sv.phase != 0) return false;
        broadcast(QUERY, e.req, {}, 0);
        sv.phase = 1; sv.req = e.req; sv.requester = e.src;
        sv.write = e.type == PUT ? e.val : NONE;
        sv.resp_present = 1u << self;
        sv.resp_seq[self] = sv.seq; sv.resp_val[self] = sv.val;
        return true;
      case QUERY:
        send(sys, ACK_QUERY, self, e.src, e.req, sv.seq, sv.val);
        return true;
      case ACK_QUERY: {
        if (sv.phase != 1 || sv.req != e.req) return false;
        // Keyed by peer: a second reply replaces the first.
        sv.resp_present |= 1u << e.src;
        sv.resp_seq[e.src] = e.seq; sv.resp_val[e.src] = e.val;
        if (__builtin_popcount(sv.resp_present) < majority()) return true;
        Seq best; u32 bval = 0; bool any = false;
        for (int j = 0; j < S; ++j)
          if (((sv.resp_present >> j) & 1) && (!any || best < sv.resp_seq[j])) {
            best = sv.resp_seq[j]; bval = sv.resp_val[j]; any = true;
          }
        u32 read = NONE;
        if (sv.write != NONE) {
          best = {best.clock + 1, self};
          bval = sv.write;
        } else {
          read = bval;
        }
        broadcast(RECORD, sv.req, best, bval);
        if (sv.seq < best) { sv.seq = best; sv.val = bval; }
        sv.phase = 2; sv.read = read; sv.write = NONE;
        sv.resp_present = 0; sv.resp_seq = {}; sv.resp_val = {};
        sv.acks = 1u << self;
        return true;
      }
      case RECORD:
        send(sys, ACK_RECORD, self, e.src, e.req);
        if (sv.seq < e.seq) { sv.seq = e.seq; sv.val = e.val; }
        return true;
      case ACK_RECORD: {
        if (sv.phase != 2 || sv.req != e.req || ((sv.acks >> e.src) & 1))
          return false;
        sv.acks |= 1u << e.src;
        if (__builtin_popcount(sv.acks) < majority()) return true;
        if (sv.read != NONE)
          send(sys, GET_OK, self, sv.requester, sv.req, {}, sv.read);
        else
          send(sys, PUT_OK, self, sv.requester, sv.req);
        sv = Server{sv.seq, sv.val};
        return true;
      }
    }
    return false;
  }

  // Calls `visit(successor)` once per transition of `st` (the successor
  // is scratch: copy it to keep it).
  template <typename Visit>
  void expand(const u32* lanes, size_t len, Visit&& visit) const {
    thread_local Sys base, n;
    thread_local State succ;
    decode(lanes, len, base);
    const size_t m = base.net.size();
    for (size_t i = 0; i < m; ++i) {
      if (i > 0 && base.net[i] == base.net[i - 1]) continue;  // distinct only
      n = base;
      n.net.erase(n.net.begin() + i);
      if (!deliver(n, Env::of(base.net[i]))) continue;
      encode(n, succ);
      visit(succ);
    }
  }

  // Direct search for a legal serialization: `pos[c]` ops of client c are
  // placed, the register holds `value`.
  bool serialize(const Sys& sys, std::array<u32, MAXC>& pos, u32 value) const {
    bool done = true;
    for (int c = 0; c < C; ++c)
      for (u32 j = pos[c]; j < sys.clients[c].n_ops; ++j)
        done &= !sys.clients[c].ops[j].returned;
    if (done) return true;
    for (int c = 0; c < C; ++c) {
      const Client& cl = sys.clients[c];
      if (pos[c] >= cl.n_ops) continue;
      const Op& op = cl.ops[pos[c]];
      bool ready = true;
      for (int j = 0; j < C; ++j)
        if (j != c && pos[j] < op.frontier[j]) ready = false;
      if (!ready) continue;
      if (!op.write && op.returned && op.ret != value) continue;
      pos[c]++;
      bool ok = serialize(sys, pos, op.write ? op.value : value);
      pos[c]--;
      if (ok) return true;
    }
    return false;
  }

  bool holds(const u32* lanes, size_t n, size_t p) const {
    if (p == 0) {
      thread_local Sys sys;
      decode(lanes, n, sys);
      std::array<u32, MAXC> pos{};
      return serialize(sys, pos, 0);
    }
    if (p == 1) {
      for (size_t i = head; i < n; ++i) {
        Env e = Env::of(lanes[i]);
        if (e.type == GET_OK && e.val != 0) return true;
      }
      return false;
    }
    return in_flight(n) <= pool_cap;
  }

  int in_flight(size_t n) const { return int(n - head); }

  bool from_fields(const std::vector<long long>& f, State& st) const {
    const size_t per_srv = 9 + 4 * S, per_cl = 2 + C, per_env = 7;
    const size_t fhead = S * per_srv + C * per_cl;
    if (f.size() < fhead || (f.size() - fhead) % per_env) return false;
    for (long long x : f)
      if (x < 0 || x > 63) return false;
    Sys sys;
    for (int s = 0; s < S; ++s) {
      const long long* g = &f[s * per_srv];
      Server& sv = sys.servers[s];
      sv.seq = {u32(g[0]), u32(g[1])}; sv.val = u32(g[2]);
      sv.phase = u32(g[3]); sv.req = u32(g[4]); sv.requester = u32(g[5]);
      sv.write = u32(g[6]); sv.read = u32(g[7]); sv.acks = u32(g[8]);
      if (sv.seq.clock > 15 || sv.seq.id > 15 || sv.val > 15 || sv.phase > 2 ||
          sv.requester > 15 || sv.write > 15 || sv.read > 15 ||
          sv.acks >= (1u << S))
        return false;
      for (int j = 0; j < S; ++j) {
        const long long* r = &g[9 + 4 * j];
        if (r[0] > 1 || r[1] > 15 || r[2] > 15 || r[3] > 15) return false;
        if (r[0]) {
          sv.resp_present |= 1u << j;
          sv.resp_seq[j] = {u32(r[1]), u32(r[2])};
          sv.resp_val[j] = u32(r[3]);
        }
      }
    }
    for (int c = 0; c < C; ++c) {
      const long long* g = &f[S * per_srv + c * per_cl];
      u32 phase = u32(g[0]), ret = u32(g[1]);
      if (phase > 2 || ret > 15) return false;
      Client& cl = sys.clients[c];
      cl.n_ops = phase >= 1 ? 2 : 1;
      cl.ops[0].write = true; cl.ops[0].value = u32(c + 1);
      cl.ops[0].returned = phase >= 1;
      if (phase >= 1) {
        for (int j = 0; j < C; ++j) {
          if (g[2 + j] > 2) return false;
          cl.ops[1].frontier[j] = u32(g[2 + j]);
        }
        cl.ops[1].returned = phase == 2;
        cl.ops[1].ret = phase == 2 ? ret : 0;
      }
    }
    for (size_t i = fhead; i < f.size(); i += per_env) {
      Env e;
      e.type = u32(f[i]); e.src = u32(f[i + 1]); e.dst = u32(f[i + 2]);
      e.req = u32(f[i + 3]); e.seq = {u32(f[i + 4]), u32(f[i + 5])};
      e.val = u32(f[i + 6]);
      if (e.type > 7 || e.src > 15 || e.dst > 15 || e.seq.clock > 15 ||
          e.seq.id > 15 || e.val > 15 || int(e.dst) >= S + C)
        return false;
      sys.net.push_back(e.code());
    }
    encode(sys, st);
    return true;
  }
};

// ---------------------------------------------------------------------------
// Multithreaded frontier BFS, as in bfs_ref.cpp, over flat buffers (a
// state's lanes vary in length with the envelopes in flight). Visited set:
// 64 shards of open-addressing fingerprint tables, each grown under its
// lock. Each property is evaluated on every unique state when it is
// popped; `min_depth[p]` is the least depth (init = 1) at which property p
// is discovered, 0 if never.
// ---------------------------------------------------------------------------

constexpr int SHARDS = 64;

struct FpSet {
  std::vector<u64> slots = std::vector<u64>(1 << 10, 0);
  size_t count = 0;

  bool insert(u64 fp, u64 h) {  // fp != 0
    if (2 * (count + 1) > slots.size()) grow();
    size_t mask = slots.size() - 1;
    for (size_t i = h & mask;; i = (i + 1) & mask) {
      if (slots[i] == fp) return false;
      if (slots[i] == 0) { slots[i] = fp; count++; return true; }
    }
  }
  void grow() {
    std::vector<u64> old(2 * slots.size(), 0);
    old.swap(slots);
    size_t mask = slots.size() - 1;
    for (u64 fp : old)
      if (fp) {
        size_t i = (mix64(fp) >> 6) & mask;
        while (slots[i]) i = (i + 1) & mask;
        slots[i] = fp;
      }
  }
};

// A level's states: each is [n, lanes...] in `data`, starting at `offs`.
struct Level {
  std::vector<u32> data;
  std::vector<size_t> offs;
  void push(const u32* lanes, size_t n) {
    offs.push_back(data.size());
    data.push_back(u32(n));
    data.insert(data.end(), lanes, lanes + n);
  }
  size_t size() const { return offs.size(); }
};

template <typename Model>
struct Bfs {
  using State = typename Model::State;
  const Model& model;
  int threads;
  u64 salt, mask;

  std::array<FpSet, SHARDS> visited;
  std::array<std::mutex, SHARDS> locks;

  u64 generated = 0;
  u64 unique = 0;
  int depth = 0;
  int max_in_flight = 0;
  std::vector<int> min_depth;
  std::vector<u64> level_unique, level_generated;

  Bfs(const Model& m, int t, int fp_bits, u64 s)
      : model(m), threads(t), salt(s),
        mask(fp_bits >= 64 ? ~0ull : ((1ull << fp_bits) - 1)),
        min_depth(Model::kinds().size(), 0) {}

  bool insert(const std::vector<u32>& lanes) {
    u64 fp = fingerprint(lanes, salt, mask);
    u64 h = mix64(fp);
    std::lock_guard<std::mutex> g(locks[h & (SHARDS - 1)]);
    return visited[h & (SHARDS - 1)].insert(fp, h >> 6);
  }

  void run() {
    Level frontier;
    for (const auto& s : model.init_states()) {
      generated++;
      if (insert(s.lanes)) frontier.push(s.lanes.data(), s.lanes.size());
    }
    unique = frontier.size();
    level_unique.push_back(unique);
    depth = 1;
    const auto kinds = Model::kinds();
    const size_t P = kinds.size();
    while (frontier.size()) {
      std::vector<Level> next(threads);
      std::vector<std::vector<char>> found(threads, std::vector<char>(P, 0));
      std::vector<int> most(threads, 0);
      std::vector<u64> gen(threads, 0);
      std::atomic<size_t> cursor{0};
      auto worker = [&](int t) {
        Level& out = next[t];
        size_t i;
        while ((i = cursor.fetch_add(1)) < frontier.size()) {
          const u32* s = &frontier.data[frontier.offs[i] + 1];
          size_t n = frontier.data[frontier.offs[i]];
          most[t] = std::max(most[t], model.in_flight(n));
          for (size_t p = 0; p < P; ++p)
            if (model.holds(s, n, p) == (kinds[p] == SOMETIMES)) found[t][p] = 1;
          model.expand(s, n, [&](const State& succ) {
            gen[t]++;
            if (insert(succ.lanes)) out.push(succ.lanes.data(), succ.lanes.size());
          });
        }
      };
      std::vector<std::thread> pool;
      for (int t = 1; t < threads; ++t) pool.emplace_back(worker, t);
      worker(0);
      for (auto& t : pool) t.join();
      u64 level_gen = 0;
      for (int t = 0; t < threads; ++t) {
        level_gen += gen[t];
        max_in_flight = std::max(max_in_flight, most[t]);
        for (size_t p = 0; p < P; ++p)
          if (found[t][p] && !min_depth[p]) min_depth[p] = depth;
      }
      generated += level_gen;
      level_generated.push_back(level_gen);
      Level merged;
      size_t total = 0, count = 0;
      for (auto& v : next) { total += v.data.size(); count += v.size(); }
      merged.data.reserve(total);
      merged.offs.reserve(count);
      for (auto& v : next) {
        size_t base = merged.data.size();
        merged.data.insert(merged.data.end(), v.data.begin(), v.data.end());
        for (size_t o : v.offs) merged.offs.push_back(base + o);
        v = Level{};
      }
      frontier = std::move(merged);
      unique += frontier.size();
      if (frontier.size()) level_unique.push_back(frontier.size());
      if (frontier.size()) depth++;
    }
  }
};

template <typename Model>
int run_bfs(const Model& model, int threads, int fp_bits, u64 salt) {
  Bfs<Model> bfs(model, threads, fp_bits, salt);
  bfs.run();
  std::printf("generated=%llu unique=%llu depth=%d\n",
              (unsigned long long)bfs.generated,
              (unsigned long long)bfs.unique, bfs.depth);
  for (size_t p = 0; p < bfs.min_depth.size(); ++p)
    std::printf("prop %zu %d\n", p, bfs.min_depth[p]);
  for (size_t d = 0; d < bfs.level_unique.size(); ++d)
    std::printf("level %zu %llu %llu\n", d + 1,
                (unsigned long long)bfs.level_unique[d],
                (unsigned long long)bfs.level_generated[d]);
  std::printf("max_in_flight %d\n", bfs.max_in_flight);
  return 0;
}

// One count per path: 1 if the first state is no init state, 1 for each
// step that is no transition of the model, 1 if the last state does not
// discover the property (sometimes: holds; always: fails), and 1 for each
// state whose fields do not decode.
template <typename Model>
int check_paths(const Model& model) {
  using State = typename Model::State;
  const auto kinds = Model::kinds();
  long long n_paths;
  if (!(std::cin >> n_paths)) return 2;
  for (long long k = 0; k < n_paths; ++k) {
    long long prop, n_states;
    if (!(std::cin >> prop >> n_states)) return 2;
    std::string line;
    std::getline(std::cin, line);
    std::vector<State> states;
    long long invalid = 0;
    for (long long i = 0; i < n_states; ++i) {
      std::getline(std::cin, line);
      std::vector<long long> f;
      size_t pos = 0;
      while (pos < line.size()) {
        size_t end = line.find(' ', pos);
        if (end == std::string::npos) end = line.size();
        if (end > pos) f.push_back(std::stoll(line.substr(pos, end - pos)));
        pos = end + 1;
      }
      State s;
      if (!model.from_fields(f, s)) { invalid++; continue; }
      states.push_back(s);
    }
    if (states.empty() || prop < 0 || prop >= (long long)kinds.size()) {
      std::printf("%lld %lld\n", invalid + 1, n_states);
      continue;
    }
    bool is_init = false;
    for (const auto& s : model.init_states())
      is_init |= s.lanes == states[0].lanes;
    invalid += !is_init;
    for (size_t i = 1; i < states.size(); ++i) {
      bool ok = false;
      const auto& last = states[i - 1].lanes;
      model.expand(last.data(), last.size(),
                   [&](const State& n) { ok |= n.lanes == states[i].lanes; });
      invalid += !ok;
    }
    const auto& end = states.back().lanes;
    bool found = model.holds(end.data(), end.size(), prop) ==
                 (kinds[prop] == SOMETIMES);
    invalid += !found;
    std::printf("%lld %lld\n", invalid, n_states);
  }
  return 0;
}

}  // namespace

int main(int argc, char** argv) {
  if (argc < 6 || std::strcmp(argv[2], "abd") != 0) {
    std::fprintf(stderr,
                 "usage: %s (bfs|path) abd C S POOL [THREADS FP_BITS SALT]\n",
                 argv[0]);
    return 2;
  }
  Abd m(std::atoi(argv[3]), std::atoi(argv[4]), std::atoi(argv[5]));
  if (std::strcmp(argv[1], "path") == 0) return check_paths(m);
  if (std::strcmp(argv[1], "bfs") != 0 || argc < 9) return 2;
  int threads = std::max(1, std::atoi(argv[6]));
  int fp_bits = std::atoi(argv[7]);
  if (fp_bits < 8 || fp_bits > 64) return 2;
  u64 salt = std::strtoull(argv[8], nullptr, 10);
  return run_bfs(m, threads, fp_bits, mix64(salt));
}
