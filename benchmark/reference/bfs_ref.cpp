// The plain reference for the benchmark's `correct` decision: a
// multithreaded breadth-first model checker over the benchmark's
// configurations (Paxos-C with 3 servers, 2PC-N), independent of the JAX
// program under test.
//
// It is a copy of stateright_tpu/_native/baseline_bfs.cpp (PR 21's tree),
// kept here so that no later PR can change it, with three additions:
//   - every property of the configuration, evaluated on every reachable
//     state, with the least BFS depth at which each is discovered
//     (sometimes: condition holds; always: condition fails);
//   - `fp_bits` below 64: dedup by a salted fingerprint cut to that many
//     bits — the control, which loses states by collision;
//   - `path` mode: reads paths (one state per line, as decoded fields) on
//     stdin and counts the steps that are not transitions of the model.
//
// Usage:
//   bfs_ref bfs (paxos C POOL | 2pc N) THREADS FP_BITS SALT
//     -> "generated=G unique=U depth=D", then "prop <i> <min depth or 0>"
//        per property, then "level <d> <unique at depth d> <generated by
//        expanding them>" per BFS level
//   bfs_ref path (paxos C POOL | 2pc N) < paths
//     stdin: "<n paths>", then per path "<prop index> <n states>" and one
//     line of fields per state; stdout: "<invalid> <n states>" per path.
//
// Model semantics: examples/paxos.rs:106-254 and examples/2pc.rs:59-147 of
// stateright, as ported in baseline_bfs.cpp.

#include <algorithm>
#include <atomic>
#include <array>
#include <cstdint>
#include <functional>
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

constexpr u32 EMPTY = 0xFFFFFFFFu;

inline u64 mix64(u64 h) {
  h = (h ^ (h >> 30)) * 0xBF58476D1CE4E5B9ull;
  h = (h ^ (h >> 27)) * 0x94D049BB133111EBull;
  return h ^ (h >> 31);
}

inline u64 fingerprint(const u32* lanes, int n, u64 salt, u64 mask) {
  u64 h = 0x5851F42D4C957F2Dull ^ salt;
  for (int i = 0; i < n; ++i)
    h = mix64(h ^ (u64(lanes[i]) + 0x9E3779B97F4A7C15ull * u64(i + 1)));
  h &= mask;
  return h ? h : 1;
}

enum Kind { ALWAYS, SOMETIMES };

// ---------------------------------------------------------------------------
// Multithreaded frontier BFS. Visited set: sharded unordered_set of
// fingerprints. Each property is evaluated on every unique state when it
// is popped; `min_depth[p]` is the least depth (init = 1) at which property
// p is discovered, 0 if never.
// ---------------------------------------------------------------------------

constexpr int SHARDS = 64;

template <typename Model>
struct Bfs {
  using State = typename Model::State;
  const Model& model;
  int threads;
  u64 salt, mask;

  std::array<std::unordered_set<u64>, SHARDS> visited;
  std::array<std::mutex, SHARDS> locks;

  std::atomic<u64> generated{0};
  u64 unique = 0;
  int depth = 0;
  std::vector<int> min_depth;
  // Per BFS level (index depth - 1): unique states at that depth, and the
  // successors generated by expanding them.
  std::vector<u64> level_unique, level_generated;

  Bfs(const Model& m, int t, int fp_bits, u64 s)
      : model(m), threads(t), salt(s),
        mask(fp_bits >= 64 ? ~0ull : ((1ull << fp_bits) - 1)),
        min_depth(Model::kinds().size(), 0) {}

  bool insert(const State& s) {
    u64 fp = fingerprint(s.lanes.data(), Model::LANES, salt, mask);
    int sh = mix64(fp) & (SHARDS - 1);
    std::lock_guard<std::mutex> g(locks[sh]);
    return visited[sh].insert(fp).second;
  }

  void run() {
    std::vector<State> frontier = model.init_states();
    generated += frontier.size();
    {
      std::vector<State> uniq;
      for (const auto& s : frontier)
        if (insert(s)) uniq.push_back(s);
      unique = uniq.size();
      level_unique.push_back(unique);
      frontier.swap(uniq);
    }
    depth = 1;
    const auto kinds = Model::kinds();
    const size_t P = kinds.size();
    while (!frontier.empty()) {
      std::vector<std::vector<State>> next_per_thread(threads);
      std::vector<std::vector<char>> found(threads, std::vector<char>(P, 0));
      std::atomic<size_t> cursor{0};
      std::atomic<u64> level_gen{0};
      auto worker = [&](int t) {
        auto& out = next_per_thread[t];
        std::vector<State> succs;
        size_t i;
        u64 local_gen = 0;
        while ((i = cursor.fetch_add(1)) < frontier.size()) {
          const State& s = frontier[i];
          for (size_t p = 0; p < P; ++p)
            if (model.holds(s, p) == (kinds[p] == SOMETIMES)) found[t][p] = 1;
          succs.clear();
          model.expand(s, succs);
          local_gen += succs.size();
          for (auto& n : succs)
            if (insert(n)) out.push_back(n);
        }
        generated += local_gen;
        level_gen += local_gen;
      };
      std::vector<std::thread> pool;
      for (int t = 1; t < threads; ++t) pool.emplace_back(worker, t);
      worker(0);
      for (auto& t : pool) t.join();
      level_generated.push_back(level_gen.load());
      for (size_t p = 0; p < P; ++p)
        for (int t = 0; t < threads; ++t)
          if (found[t][p] && !min_depth[p]) min_depth[p] = depth;
      frontier.clear();
      for (auto& v : next_per_thread) {
        unique += v.size();
        frontier.insert(frontier.end(), v.begin(), v.end());
        v.clear();
      }
      if (!frontier.empty()) level_unique.push_back(frontier.size());
      if (!frontier.empty()) depth++;
    }
  }
};

// ---------------------------------------------------------------------------
// 2PC (ref: examples/2pc.rs:59-147). Packed fields in lanes[0..2]:
//   lane0: rm_state, 2 bits per RM (0 working, 1 prepared, 2 committed,
//          3 aborted); lane1: tm_state (0 init, 1 committed, 2 aborted) |
//          tm_prepared << 2; lane2: msgs: bit0 commit, bit1 abort,
//          bit(2+i) prepared_i.
// Properties (ref: examples/2pc.rs:125-146): "abort agreement" and
// "commit agreement" (sometimes), "consistent" (always).
// Path fields per state: rm_0..rm_{N-1} tm prepared_mask commit abort
// prepared_msg_0..prepared_msg_{N-1}.
// ---------------------------------------------------------------------------

struct TwoPhase {
  static constexpr int LANES = 4;
  struct State { std::array<u32, LANES> lanes; };
  int rms;

  explicit TwoPhase(int n) : rms(n) {
    if (n > 14) { std::fprintf(stderr, "2pc: rms > 14\n"); std::exit(2); }
  }

  static std::vector<Kind> kinds() { return {SOMETIMES, SOMETIMES, ALWAYS}; }

  std::vector<State> init_states() const {
    State s{};
    return {s};
  }

  static u32 rm(const State& s, int i) { return (s.lanes[0] >> (2 * i)) & 3u; }
  static void set_rm(State& s, int i, u32 v) {
    s.lanes[0] = (s.lanes[0] & ~(3u << (2 * i))) | (v << (2 * i));
  }

  void expand(const State& s, std::vector<State>& out) const {
    u32 tm = s.lanes[1] & 3u;
    u32 prep = s.lanes[1] >> 2;
    u32 msgs = s.lanes[2];
    bool all_prep = prep == ((1u << rms) - 1u);
    if (tm == 0 && all_prep) {  // tm_commit
      State n = s; n.lanes[1] = 1u | (prep << 2); n.lanes[2] = msgs | 1u;
      out.push_back(n);
    }
    if (tm == 0) {  // tm_abort
      State n = s; n.lanes[1] = 2u | (prep << 2); n.lanes[2] = msgs | 2u;
      out.push_back(n);
    }
    for (int i = 0; i < rms; ++i) {
      if (tm == 0 && (msgs >> (2 + i)) & 1u) {  // tm_rcv_prepared
        State n = s; n.lanes[1] = tm | ((prep | (1u << i)) << 2);
        out.push_back(n);
      }
      if (rm(s, i) == 0) {  // working: rm_prepare, rm_choose_abort
        State n = s; set_rm(n, i, 1); n.lanes[2] = msgs | (1u << (2 + i));
        out.push_back(n);
        State a = s; set_rm(a, i, 3);
        out.push_back(a);
      }
      if (msgs & 1u) { State n = s; set_rm(n, i, 2); out.push_back(n); }
      if (msgs & 2u) { State n = s; set_rm(n, i, 3); out.push_back(n); }
    }
  }

  bool holds(const State& s, size_t p) const {
    bool all_abort = true, all_commit = true, any_abort = false,
         any_commit = false;
    for (int i = 0; i < rms; ++i) {
      all_abort &= rm(s, i) == 3;
      all_commit &= rm(s, i) == 2;
      any_abort |= rm(s, i) == 3;
      any_commit |= rm(s, i) == 2;
    }
    if (p == 0) return all_abort;
    if (p == 1) return all_commit;
    return !(any_abort && any_commit);
  }

  bool from_fields(const std::vector<long long>& f, State& s) const {
    if (int(f.size()) != 2 * rms + 4) return false;
    s = State{};
    for (int i = 0; i < rms; ++i) {
      if (f[i] < 0 || f[i] > 3) return false;
      set_rm(s, i, u32(f[i]));
    }
    long long tm = f[rms], prep = f[rms + 1];
    if (tm < 0 || tm > 2 || prep < 0 || prep >= (1ll << rms)) return false;
    s.lanes[1] = u32(tm) | (u32(prep) << 2);
    u32 msgs = 0;
    for (int j = 0; j < rms + 2; ++j) {
      if (f[rms + 2 + j] != 0 && f[rms + 2 + j] != 1) return false;
      if (f[rms + 2 + j]) msgs |= 1u << j;
    }
    s.lanes[2] = msgs;
    return true;
  }
};

// ---------------------------------------------------------------------------
// Paxos (ref: examples/paxos.rs:106-254): C clients, 3 servers, unordered
// non-duplicating network, linearizability-tested register. State: [srvA,
// srvB] x 3, client lane, sorted envelope pool (EMPTY-padded).
// Properties (ref: examples/paxos.rs:280-300): "linearizable" (always),
// "value chosen" (sometimes), and "pool capacity" (always: at most POOL
// envelopes in flight — the program under test holds POOL slots).
// Path fields per state: per server ballot proposal accepted decided
// accepts present_0 la_0 present_1 la_1 present_2 la_2; per client phase
// ret frontier; then the envelope ids in flight.
// ---------------------------------------------------------------------------

constexpr int S = 3;
constexpr int MAXPOOL = 24;

struct Paxos {
  static constexpr int LANES = 2 * S + 1 + MAXPOOL;
  struct State { std::array<u32, LANES> lanes; };

  int C;
  int NB, NLA, bb, bla, bprep, maj;
  int off_prop, off_acc, off_dec, off_accs;

  // Envelope vocabulary (mirrors tensor/paxos.py _build_vocab).
  int PUT0, GET0, PUTOK0, GETOK0, PREPARE0, PREPARED0, ACCEPT0, ACCEPTED0,
      DECIDED0, V;
  std::vector<u32> TYP, DST, BAL, PROP, LA, SRC, VAL;

  // Linearizability combo tables (mirrors _build_lin_tables).
  struct Combo {
    std::array<u32, 3> phase_mask;          // allowed phases per client
    std::array<int, 3> ret;                 // expected Get value; -1 free
    std::array<std::array<u32, 3>, 3> maxf; // frontier cap [client][peer]
  };
  std::vector<Combo> combos;

  int pool_cap = 0;

  explicit Paxos(int clients) : C(clients) {
    if (C > 3) { std::fprintf(stderr, "paxos: clients > 3\n"); std::exit(2); }
    NB = 1 + C * S;
    NLA = 1 + C * S * C;
    auto bits = [](int n) { int b = 0; while ((1 << b) < n) b++; return b ? b : 1; };
    bb = bits(NB); bla = bits(NLA); bprep = 1 + bla; maj = S / 2 + 1;
    off_prop = bb; off_acc = bb + 2; off_dec = off_acc + bla;
    off_accs = off_dec + 1;
    build_vocab();
    build_lin_tables();
  }

  void build_vocab() {
    int NBALLOT = C * S;
    PUT0 = 0;
    GET0 = PUT0 + C;
    PUTOK0 = GET0 + C;
    GETOK0 = PUTOK0 + S * C;
    PREPARE0 = GETOK0 + C * C;
    PREPARED0 = PREPARE0 + NBALLOT * (S - 1);
    ACCEPT0 = PREPARED0 + NBALLOT * (S - 1) * NLA;
    ACCEPTED0 = ACCEPT0 + NBALLOT * C * (S - 1);
    DECIDED0 = ACCEPTED0 + NBALLOT * (S - 1);
    V = DECIDED0 + NBALLOT * C * (S - 1);
    TYP.assign(V, 0); DST.assign(V, 0); BAL.assign(V, 0); PROP.assign(V, 0);
    LA.assign(V, 0); SRC.assign(V, 0); VAL.assign(V, 0);
    auto leader = [&](int b) { return (b - 1) % S; };
    auto peer = [&](int l, int d) { return d + (d >= l ? 1 : 0); };
    for (int k = 0; k < C; ++k) {
      int i = PUT0 + k;
      TYP[i] = 0; DST[i] = (S + k) % S; PROP[i] = k; SRC[i] = S + k;
      i = GET0 + k;
      TYP[i] = 1; DST[i] = (S + k + 1) % S; PROP[i] = k; SRC[i] = S + k;
    }
    for (int s = 0; s < S; ++s)
      for (int k = 0; k < C; ++k) {
        int i = PUTOK0 + s * C + k;
        TYP[i] = 2; DST[i] = k; PROP[i] = k; SRC[i] = s;
      }
    for (int k = 0; k < C; ++k)
      for (int v = 0; v < C; ++v) {
        int i = GETOK0 + k * C + v;
        TYP[i] = 3; DST[i] = k; PROP[i] = k; VAL[i] = v;
        SRC[i] = (S + k + 1) % S;
      }
    for (int b = 1; b <= NBALLOT; ++b)
      for (int d = 0; d < S - 1; ++d) {
        int i = PREPARE0 + (b - 1) * (S - 1) + d;
        TYP[i] = 4; DST[i] = peer(leader(b), d); BAL[i] = b; SRC[i] = leader(b);
        for (int la = 0; la < NLA; ++la) {
          int j = PREPARED0 + ((b - 1) * (S - 1) + d) * NLA + la;
          TYP[j] = 5; DST[j] = leader(b); BAL[j] = b; LA[j] = la;
          SRC[j] = peer(leader(b), d);
        }
        i = ACCEPTED0 + (b - 1) * (S - 1) + d;
        TYP[i] = 7; DST[i] = leader(b); BAL[i] = b; SRC[i] = peer(leader(b), d);
        for (int k = 0; k < C; ++k) {
          i = ACCEPT0 + ((b - 1) * C + k) * (S - 1) + d;
          TYP[i] = 6; DST[i] = peer(leader(b), d); BAL[i] = b; PROP[i] = k;
          SRC[i] = leader(b);
          i = DECIDED0 + ((b - 1) * C + k) * (S - 1) + d;
          TYP[i] = 8; DST[i] = peer(leader(b), d); BAL[i] = b; PROP[i] = k;
          SRC[i] = leader(b);
        }
      }
  }

  void build_lin_tables() {
    // Enumerate per-client op-inclusion patterns (0: put in flight; 1: put
    // done, get not completed; 2: get included) x all order interleavings,
    // replay the register, and compile to constraint rows
    // (ref: src/semantics/linearizability.rs:193-280 — here the search is
    // precompiled because the workload's history shape is static).
    constexpr int NULLV = -2;
    std::vector<std::array<int, 3>> prefixes;
    std::array<int, 3> cur{};
    std::function<void(int)> gen = [&](int c) {
      if (c == C) { prefixes.push_back(cur); return; }
      for (int p = 0; p < 3; ++p) { cur[c] = p; gen(c + 1); }
    };
    gen(0);
    std::vector<Combo> all;
    for (auto& pre : prefixes) {
      std::vector<std::pair<int, char>> ops;
      for (int c = 0; c < C; ++c) {
        if (pre[c] >= 1) ops.emplace_back(c, 'p');
        if (pre[c] == 2) ops.emplace_back(c, 'g');
      }
      std::vector<std::vector<std::pair<int, char>>> seqs{{}};
      for (size_t n = 0; n < ops.size(); ++n) {
        std::vector<std::vector<std::pair<int, char>>> nxt;
        for (auto& seq : seqs) {
          auto used = [&](std::pair<int, char> op) {
            for (auto& o : seq) if (o == op) return true;
            return false;
          };
          for (auto& op : ops) {
            if (used(op)) continue;
            if (op.second == 'g' && !used({op.first, 'p'})) continue;
            auto s2 = seq; s2.push_back(op); nxt.push_back(s2);
          }
        }
        seqs.swap(nxt);
      }
      if (seqs.empty()) seqs = {{}};
      for (auto& seq : seqs) {
        Combo cb{};
        for (int c = 0; c < C; ++c) {
          if (pre[c] == 0) cb.phase_mask[c] = 1u << 0;
          else if (pre[c] == 1) cb.phase_mask[c] = (1u << 0) | (1u << 1);
          else cb.phase_mask[c] = (1u << 1) | (1u << 2);
        }
        int val = NULLV;
        std::array<int, 3> expected{NULLV, NULLV, NULLV};
        for (auto& [c, kind] : seq) {
          if (kind == 'p') val = c; else expected[c] = val;
        }
        for (int c = 0; c < C; ++c) {
          if (pre[c] == 2) cb.ret[c] = expected[c] == NULLV ? -1 : expected[c];
          else cb.ret[c] = -1;
        }
        for (int c = 0; c < C; ++c)
          for (int p = 0; p < C; ++p) cb.maxf[c][p] = 2;
        for (int c = 0; c < C; ++c) {
          if (pre[c] != 2) continue;
          size_t gpos = 0;
          for (size_t i = 0; i < seq.size(); ++i)
            if (seq[i] == std::make_pair(c, 'g')) { gpos = i; break; }
          for (int c2 = 0; c2 < C; ++c2) {
            if (c2 == c) continue;
            bool putb = false, getb = false;
            for (size_t i = 0; i < gpos; ++i) {
              if (seq[i] == std::make_pair(c2, 'p')) putb = true;
              if (seq[i] == std::make_pair(c2, 'g')) getb = true;
            }
            if (!putb) cb.maxf[c][c2] = 0;
            else if (!getb) cb.maxf[c][c2] = 1;
          }
        }
        all.push_back(cb);
      }
    }
    // Dedup identical constraint rows.
    for (auto& cb : all) {
      bool dup = false;
      for (auto& e : combos)
        if (std::memcmp(&e, &cb, sizeof(Combo)) == 0) { dup = true; break; }
      if (!dup) combos.push_back(cb);
    }
  }

  // -- field packing ---------------------------------------------------------

  struct Srv { u32 ballot, prop, accepted, decided, accepts; };
  Srv unpack(u32 a) const {
    return {a & ((1u << bb) - 1), (a >> off_prop) & 3u,
            (a >> off_acc) & ((1u << bla) - 1), (a >> off_dec) & 1u,
            (a >> off_accs) & ((1u << S) - 1)};
  }
  u32 pack(const Srv& s) const {
    return s.ballot | (s.prop << off_prop) | (s.accepted << off_acc) |
           (s.decided << off_dec) | (s.accepts << off_accs);
  }
  u32 r_of(u32 b) const { return b == 0 ? 0 : (b - 1) / S + 1; }

  std::vector<State> init_states() const {
    State s{};
    for (int i = 0; i < MAXPOOL; ++i) s.lanes[2 * S + 1 + i] = EMPTY;
    for (int k = 0; k < C; ++k) s.lanes[2 * S + 1 + k] = u32(PUT0 + k);
    return {s};
  }

  void expand(const State& st, std::vector<State>& out) const {
    const u32* pool = &st.lanes[2 * S + 1];
    u32 clients = st.lanes[2 * S];
    for (int slot = 0; slot < MAXPOOL; ++slot) {
      u32 e = pool[slot];
      if (e == EMPTY) break;                      // sorted: EMPTY at the end
      if (slot > 0 && pool[slot - 1] == e) continue;  // one Deliver per distinct
      u32 typ = TYP[e], dst = DST[e], bal = BAL[e], prp = PROP[e],
          lam = LA[e], src = SRC[e], val = VAL[e];
      bool is_server = typ == 0 || typ == 1 || typ >= 4;
      Srv sv = unpack(is_server ? st.lanes[2 * dst] : 0);
      u32 sB = is_server ? st.lanes[2 * dst + 1] : 0;
      u32 cfield = is_server ? 0 : (clients >> (8 * dst)) & 0xFFu;
      u32 cphase = cfield & 3u;
      bool not_dec = sv.decided == 0;

      Srv nv = sv; u32 nB = sB; u32 ncf = cfield;
      u32 em[3] = {EMPTY, EMPTY, EMPTY};
      bool ok = false;

      switch (typ) {
        case 0:  // Put (ref: examples/paxos.rs:163-183)
          if (not_dec && sv.prop == 0) {
            u32 nb = 1 + r_of(sv.ballot) * S + dst;
            nv = {nb, prp + 1, sv.accepted, 0, 0};
            nB = (1u | (sv.accepted << 1)) << (dst * bprep);
            em[0] = u32(PREPARE0 + (nb - 1) * (S - 1));
            em[1] = em[0] + 1;
            ok = true;
          }
          break;
        case 1:  // Get — reply only when decided (ref: paxos.rs:145-157)
          if (!not_dec) {
            u32 vprop = sv.accepted > 0 ? (sv.accepted - 1) % C : 0;
            em[0] = u32(GETOK0 + prp * C + vprop);
            ok = true;
          }
          break;
        case 4:  // Prepare (ref: paxos.rs:186-192)
          if (not_dec && sv.ballot < bal) {
            nv = {bal, sv.prop, sv.accepted, 0, sv.accepts};
            u32 lead = (bal - 1) % S;
            u32 slot2 = dst - (dst > lead ? 1 : 0);
            em[0] = u32(PREPARED0 + ((bal - 1) * (S - 1) + slot2) * NLA +
                        sv.accepted);
            ok = true;
          }
          break;
        case 5: {  // Prepared (ref: paxos.rs:193-231)
          if (not_dec && bal == sv.ballot) {
            u32 pbit = 1u << (src * bprep);
            bool already = (sB & pbit) != 0;
            u32 addB = sB | pbit | (lam << (src * bprep + 1));
            u32 pres = 0, best_la = 0;
            for (int j = 0; j < S; ++j) {
              u32 pj = (addB >> (j * bprep)) & 1u;
              u32 laj = (addB >> (j * bprep + 1)) & ((1u << bla) - 1);
              pres += pj;
              if (pj && laj > best_la) best_la = laj;
            }
            bool quorum = !already && pres == u32(maj);
            u32 chosen = best_la > 0 ? (best_la - 1) % C : sv.prop - 1;
            if (quorum) {
              em[0] = u32(ACCEPT0 + ((bal - 1) * C + chosen) * (S - 1));
              em[1] = em[0] + 1;
              nv = {sv.ballot, chosen + 1, 1 + (bal - 1) * u32(C) + chosen, 0,
                    1u << dst};
            } else {
              nv = {sv.ballot, sv.prop, sv.accepted, 0, sv.accepts};
            }
            nB = addB;
            ok = true;
          }
          break;
        }
        case 6:  // Accept (ref: paxos.rs:232-240)
          if (not_dec && sv.ballot <= bal) {
            nv = {bal, sv.prop, 1 + (bal - 1) * u32(C) + prp, 0, sv.accepts};
            u32 lead = (bal - 1) % S;
            u32 slot2 = dst - (dst > lead ? 1 : 0);
            em[0] = u32(ACCEPTED0 + (bal - 1) * (S - 1) + slot2);
            ok = true;
          }
          break;
        case 7: {  // Accepted (ref: paxos.rs:241-263)
          if (not_dec && bal == sv.ballot) {
            u32 abit = 1u << src;
            u32 naccs = sv.accepts | abit;
            u32 cnt = 0;
            for (int j = 0; j < S; ++j) cnt += (naccs >> j) & 1u;
            bool aq = !(sv.accepts & abit) && cnt == u32(maj);
            if (aq) {
              em[0] = u32(DECIDED0 + ((bal - 1) * C + (sv.prop - 1)) * (S - 1));
              em[1] = em[0] + 1;
              em[2] = u32(PUTOK0 + dst * C + (sv.prop - 1));
            }
            nv = {sv.ballot, sv.prop, sv.accepted, aq ? 1u : 0u, naccs};
            ok = true;
          }
          break;
        }
        case 8:  // Decided (ref: paxos.rs:264-271)
          if (not_dec) {
            nv = {bal, sv.prop, 1 + (bal - 1) * u32(C) + prp, 1, sv.accepts};
            ok = true;
          }
          break;
        case 2:  // PutOk -> client issues Get, captures real-time frontier
          if (cphase == 0) {
            u32 frontier = 0, fshift = 0;
            for (int c2 = 0; c2 < C; ++c2) {
              if (u32(c2) == dst) continue;
              u32 f2 = (clients >> (8 * c2)) & 3u;
              u32 comp = f2 == 2 ? 2 : (f2 == 1 ? 1 : 0);
              frontier |= comp << fshift;
              fshift += 2;
            }
            ncf = 1u | (frontier << 4);
            em[0] = u32(GET0 + dst);
            ok = true;
          }
          break;
        case 3:  // GetOk -> client done
          if (cphase == 1) {
            ncf = (cfield & ~3u & ~(3u << 2)) | 2u | (val << 2);
            ok = true;
          }
          break;
      }
      if (!ok) continue;

      State n = st;
      if (is_server) {
        n.lanes[2 * dst] = pack(nv);
        n.lanes[2 * dst + 1] = nB;
      } else {
        n.lanes[2 * S] = (clients & ~(0xFFu << (8 * dst))) | (ncf << (8 * dst));
      }
      // Pool: drop delivered instance, add emissions, re-sort.
      u32* np = &n.lanes[2 * S + 1];
      int cnt = 0;
      u32 tmp[MAXPOOL + 3];
      for (int i = 0; i < MAXPOOL; ++i)
        if (i != slot && pool[i] != EMPTY) tmp[cnt++] = pool[i];
      for (int i = 0; i < 3; ++i)
        if (em[i] != EMPTY) tmp[cnt++] = em[i];
      if (cnt > MAXPOOL) { std::fprintf(stderr, "pool overflow\n"); std::exit(3); }
      std::sort(tmp, tmp + cnt);
      for (int i = 0; i < cnt; ++i) np[i] = tmp[i];
      for (int i = cnt; i < MAXPOOL; ++i) np[i] = EMPTY;
      out.push_back(n);
    }
  }

  bool linearizable(const State& st) const {
    u32 clients = st.lanes[2 * S];
    std::array<u32, 3> phase{}, ret{};
    std::array<std::array<u32, 3>, 3> frontier{};
    for (int c = 0; c < C; ++c) {
      phase[c] = (clients >> (8 * c)) & 3u;
      ret[c] = (clients >> (8 * c + 2)) & 3u;
      for (int c2 = 0; c2 < C; ++c2) {
        if (c2 == c) { frontier[c][c2] = 0; continue; }
        int pslot = c2 - (c2 > c ? 1 : 0);
        frontier[c][c2] = (clients >> (8 * c + 4 + 2 * pslot)) & 3u;
      }
    }
    for (auto& cb : combos) {
      bool okc = true;
      for (int c = 0; c < C && okc; ++c) {
        if (!((cb.phase_mask[c] >> phase[c]) & 1u)) { okc = false; break; }
        bool has_get = (cb.phase_mask[c] & (1u << 2)) != 0;
        if (has_get && phase[c] != 1 &&
            !(cb.ret[c] >= 0 && ret[c] == u32(cb.ret[c]))) {
          okc = false;
          break;
        }
        for (int c2 = 0; c2 < C; ++c2)
          if (frontier[c][c2] > cb.maxf[c][c2]) { okc = false; break; }
      }
      if (okc) return true;
    }
    return false;
  }

  static std::vector<Kind> kinds() { return {ALWAYS, SOMETIMES, ALWAYS}; }

  int in_flight(const State& st) const {
    int n = 0;
    for (int i = 0; i < MAXPOOL; ++i) n += st.lanes[2 * S + 1 + i] != EMPTY;
    return n;
  }

  bool holds(const State& st, size_t p) const {
    if (p == 0) return linearizable(st);
    if (p == 1) {  // "value chosen": a GetOk is in flight
      for (int i = 0; i < MAXPOOL; ++i) {
        u32 e = st.lanes[2 * S + 1 + i];
        if (e != EMPTY && int(e) >= GETOK0 && int(e) < GETOK0 + C * C)
          return true;
      }
      return false;
    }
    return in_flight(st) <= pool_cap;
  }

  bool from_fields(const std::vector<long long>& f, State& st) const {
    const int head = S * 11 + C * 3;
    if (int(f.size()) < head || int(f.size()) > head + MAXPOOL) return false;
    for (long long x : f)
      if (x < 0 || x > 0xFFFFFFFFll) return false;
    st = State{};
    for (int s = 0; s < S; ++s) {
      const long long* g = &f[11 * s];
      if (g[0] >= (1ll << bb) || g[1] > 3 || g[2] >= (1ll << bla) ||
          g[3] > 1 || g[4] >= (1ll << S))
        return false;
      st.lanes[2 * s] = pack({u32(g[0]), u32(g[1]), u32(g[2]), u32(g[3]),
                              u32(g[4])});
      u32 b = 0;
      for (int j = 0; j < S; ++j) {
        if (g[5 + 2 * j] > 1 || g[6 + 2 * j] >= (1ll << bla)) return false;
        b |= (u32(g[5 + 2 * j]) | (u32(g[6 + 2 * j]) << 1)) << (j * bprep);
      }
      st.lanes[2 * s + 1] = b;
    }
    u32 clients = 0;
    for (int c = 0; c < C; ++c) {
      const long long* g = &f[11 * S + 3 * c];
      if (g[0] > 3 || g[1] > 3 || g[2] >= 16) return false;
      clients |= (u32(g[0]) | (u32(g[1]) << 2) | (u32(g[2]) << 4)) << (8 * c);
    }
    st.lanes[2 * S] = clients;
    std::vector<u32> pool;
    for (size_t i = head; i < f.size(); ++i) {
      if (f[i] >= V) return false;
      pool.push_back(u32(f[i]));
    }
    std::sort(pool.begin(), pool.end());
    for (int i = 0; i < MAXPOOL; ++i)
      st.lanes[2 * S + 1 + i] = i < int(pool.size()) ? pool[i] : EMPTY;
    return true;
  }
};

// ---------------------------------------------------------------------------

template <typename Model>
int run_bfs(const Model& model, int threads, int fp_bits, u64 salt) {
  Bfs<Model> bfs(model, threads, fp_bits, salt);
  bfs.run();
  std::printf("generated=%llu unique=%llu depth=%d\n",
              (unsigned long long)bfs.generated.load(),
              (unsigned long long)bfs.unique, bfs.depth);
  for (size_t p = 0; p < bfs.min_depth.size(); ++p)
    std::printf("prop %zu %d\n", p, bfs.min_depth[p]);
  for (size_t d = 0; d < bfs.level_unique.size(); ++d)
    std::printf("level %zu %llu %llu\n", d + 1,
                (unsigned long long)bfs.level_unique[d],
                (unsigned long long)bfs.level_generated[d]);
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
    std::vector<State> succs;
    for (size_t i = 1; i < states.size(); ++i) {
      succs.clear();
      model.expand(states[i - 1], succs);
      bool ok = false;
      for (const auto& n : succs) ok |= n.lanes == states[i].lanes;
      invalid += !ok;
    }
    bool found = model.holds(states.back(), prop) == (kinds[prop] == SOMETIMES);
    invalid += !found;
    std::printf("%lld %lld\n", invalid, n_states);
  }
  return 0;
}

template <typename Model>
int dispatch(const Model& model, int argc, char** argv, int next) {
  if (std::strcmp(argv[1], "path") == 0) return check_paths(model);
  if (argc < next + 3) return 2;
  int threads = std::max(1, std::atoi(argv[next]));
  int fp_bits = std::atoi(argv[next + 1]);
  if (fp_bits < 8 || fp_bits > 64) return 2;
  u64 salt = std::strtoull(argv[next + 2], nullptr, 10);
  return run_bfs(model, threads, fp_bits, mix64(salt));
}

}  // namespace

int main(int argc, char** argv) {
  if (argc < 4) {
    std::fprintf(stderr,
                 "usage: %s (bfs|path) (paxos C POOL | 2pc N) "
                 "[THREADS FP_BITS SALT]\n", argv[0]);
    return 2;
  }
  if (std::strcmp(argv[2], "paxos") == 0) {
    if (argc < 5) return 2;
    Paxos m(std::atoi(argv[3]));
    m.pool_cap = std::atoi(argv[4]);
    return dispatch(m, argc, argv, 5);
  }
  if (std::strcmp(argv[2], "2pc") == 0) {
    TwoPhase m(std::atoi(argv[3]));
    return dispatch(m, argc, argv, 4);
  }
  std::fprintf(stderr, "unknown model %s\n", argv[2]);
  return 2;
}
