// Unit tests for the native master's pure logic: JSON, hparam sampling,
// searcher state machines (ASHA promote semantics, snapshot/restore), and
// the scheduler's fitting function.
//
// Reference discipline: master/pkg/searcher/*_test.go +
// rm/agentrm/fitting_test.go run under `go test -race`; here the same
// binary is built plain and under -fsanitize=thread / address
// (`make -C native test tsan asan`), driven from pytest
// (tests/test_native_unit.py).

#include <cstdio>
#include <cstdlib>
#include <functional>
#include <map>
#include <random>
#include <set>
#include <string>
#include <vector>

#include "../agent/backoff.h"
#include "../common/faultpoint.h"
#include "../common/json.h"
#include "../master/preflight.h"
#include "../master/scheduler_fit.h"
#include "../master/searcher.h"

using det::Json;
using det::SearcherOp;

static int g_failures = 0;
static int g_checks = 0;

#define CHECK(cond)                                                         \
  do {                                                                      \
    ++g_checks;                                                             \
    if (!(cond)) {                                                          \
      ++g_failures;                                                         \
      std::fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__, __LINE__, #cond);  \
    }                                                                       \
  } while (0)

#define CHECK_EQ(a, b)                                                      \
  do {                                                                      \
    ++g_checks;                                                             \
    if (!((a) == (b))) {                                                    \
      ++g_failures;                                                         \
      std::fprintf(stderr, "FAIL %s:%d: %s == %s\n", __FILE__, __LINE__,    \
                   #a, #b);                                                 \
    }                                                                       \
  } while (0)

// ---------------------------------------------------------------- JSON

static void test_json_roundtrip() {
  const char* src =
      "{\"a\": 1, \"b\": -2.5e3, \"c\": [true, false, null], "
      "\"d\": {\"nested\": \"va\\\"lue\\n\"}, \"e\": \"\\u0041\"}";
  Json j = Json::parse(src);
  CHECK_EQ(j["a"].as_int(), 1);
  CHECK(j["b"].as_double() == -2500.0);
  CHECK_EQ(j["c"].as_array().size(), static_cast<size_t>(3));
  CHECK(j["c"].as_array()[0].as_bool());
  CHECK_EQ(j["d"]["nested"].as_string(), "va\"lue\n");
  CHECK_EQ(j["e"].as_string(), "A");
  // dump → parse → dump is stable
  std::string d1 = j.dump();
  Json j2 = Json::parse(d1);
  CHECK_EQ(d1, j2.dump());
}

static void test_json_malformed() {
  bool threw = false;
  try {
    Json::parse("{\"unterminated\": ");
  } catch (const std::exception&) {
    threw = true;
  }
  CHECK(threw);
}

static void test_json_defaults() {
  Json j = Json::parse("{}");
  CHECK_EQ(j["missing"].as_int(7), 7);
  CHECK_EQ(j["missing"].as_string("x"), "x");
  CHECK(j["missing"].is_null());
}

// ------------------------------------------------------------- hparams

static Json hp_spec() {
  // log hparams: minval/maxval are EXPONENTS of base (reference
  // schemas/expconf/v0/hyperparameter.json semantics).
  return Json::parse(R"({
    "lr": {"type": "log", "minval": -4, "maxval": -1, "base": 10},
    "units": {"type": "int", "minval": 8, "maxval": 64},
    "act": {"type": "categorical", "vals": ["relu", "gelu"]},
    "depth": {"type": "const", "val": 3},
    "bare": 42
  })");
}

static void test_sample_hparams() {
  std::mt19937_64 rng(1234);
  Json s = det::sample_hparams(hp_spec(), rng);
  double lr = s["lr"].as_double();
  CHECK(lr >= 1e-4 && lr <= 1e-1);
  int64_t units = s["units"].as_int();
  CHECK(units >= 8 && units <= 64);
  std::string act = s["act"].as_string();
  CHECK(act == "relu" || act == "gelu");
  CHECK_EQ(s["depth"].as_int(), 3);
  CHECK_EQ(s["bare"].as_int(), 42);
  // determinism: same seed, same sample
  std::mt19937_64 rng2(1234);
  CHECK_EQ(det::sample_hparams(hp_spec(), rng2).dump(), s.dump());
}

static void test_grid_points() {
  Json spec = Json::parse(R"({
    "lr": {"type": "double", "minval": 0.0, "maxval": 1.0, "count": 3},
    "act": {"type": "categorical", "vals": ["a", "b"]}
  })");
  auto pts = det::grid_points(spec);
  CHECK_EQ(pts.size(), static_cast<size_t>(6));
  std::set<std::string> seen;
  for (const auto& p : pts) seen.insert(p.dump());
  CHECK_EQ(seen.size(), static_cast<size_t>(6));
}

// ------------------------------------------------------------ searcher

static Json searcher_cfg(const char* extra) {
  std::string base = std::string(
      "{\"name\": \"async_halving\", \"metric\": \"loss\", "
      "\"smaller_is_better\": true, \"max_length\": {\"batches\": 16}, "
      "\"num_rungs\": 2, \"divisor\": 4, \"max_trials\": 8") + extra + "}";
  return Json::parse(base);
}

static void test_single_searcher() {
  Json cfg = Json::parse(
      "{\"name\": \"single\", \"metric\": \"loss\", "
      "\"max_length\": {\"batches\": 10}}");
  det::Searcher s(cfg, hp_spec(), 7);
  auto ops = s.initial_operations();
  // one Create + one ValidateAfter(10)
  CHECK_EQ(ops.size(), static_cast<size_t>(2));
  CHECK(ops[0].kind == SearcherOp::Kind::Create);
  CHECK(ops[1].kind == SearcherOp::Kind::ValidateAfter);
  CHECK_EQ(ops[1].length, 10);
  auto done = s.validation_completed(ops[0].request_id, 0.5, 10);
  bool saw_close = false;
  for (const auto& op : done) {
    saw_close |= op.kind == SearcherOp::Kind::Close;
  }
  CHECK(saw_close);
}

static void test_asha_promote_semantics() {
  det::Searcher s(searcher_cfg(""), hp_spec(), 7);
  auto ops = s.initial_operations();
  // Collect created trials + their first ValidateAfter (rung 0 = 16/4 = 4).
  std::vector<std::string> rids;
  int64_t rung0 = 0;
  for (const auto& op : ops) {
    if (op.kind == SearcherOp::Kind::Create) rids.push_back(op.request_id);
    if (op.kind == SearcherOp::Kind::ValidateAfter) rung0 = op.length;
  }
  CHECK(!rids.empty());
  CHECK_EQ(rung0, 4);

  // Report rung-0 metrics: trial i gets metric i (smaller better). The
  // best 1/divisor (=1/4) get promoted to the top rung — lengths are
  // CUMULATIVE (continuation-style: rung0 4 + 16 more = 20), keeping
  // promotions warm-slice continuations instead of kill+respawn.
  int promotions = 0, closes = 0;
  std::set<std::string> promoted;
  for (size_t i = 0; i < rids.size(); ++i) {
    auto out = s.validation_completed(rids[i], static_cast<double>(i), 4);
    for (const auto& op : out) {
      if (op.kind == SearcherOp::Kind::ValidateAfter) {
        CHECK_EQ(op.length, 20);
        ++promotions;
        promoted.insert(op.request_id);
      }
      if (op.kind == SearcherOp::Kind::Close) ++closes;
      // new trials may also be created (async) — allowed
    }
  }
  CHECK(promotions >= 1);
  // The FIRST reported (best metric 0) must be among the promoted.
  CHECK(promoted.count(rids[0]) == 1);
  CHECK(closes >= 1);
}

static void test_asha_snapshot_restore_determinism() {
  det::Searcher a(searcher_cfg(""), hp_spec(), 99);
  auto ops = a.initial_operations();
  std::vector<std::string> rids;
  for (const auto& op : ops) {
    if (op.kind == SearcherOp::Kind::Create) rids.push_back(op.request_id);
  }
  // half-way: report two metrics, snapshot, then diverge-check
  a.validation_completed(rids[0], 0.3, 4);
  Json snap = a.snapshot();

  det::Searcher b(searcher_cfg(""), hp_spec(), 99);
  b.restore(snap);
  auto out_a = a.validation_completed(rids[1], 0.1, 4);
  auto out_b = b.validation_completed(rids[1], 0.1, 4);
  CHECK_EQ(out_a.size(), out_b.size());
  for (size_t i = 0; i < out_a.size() && i < out_b.size(); ++i) {
    CHECK_EQ(out_a[i].to_json().dump(), out_b[i].to_json().dump());
  }
}

static void test_adaptive_asha_brackets() {
  Json cfg = Json::parse(
      "{\"name\": \"adaptive_asha\", \"metric\": \"loss\", "
      "\"smaller_is_better\": true, \"max_length\": {\"batches\": 64}, "
      "\"max_trials\": 8, \"max_rungs\": 3, \"divisor\": 4, "
      "\"mode\": \"standard\", \"max_concurrent_trials\": 8}");
  det::Searcher s(cfg, hp_spec(), 5);
  auto ops = s.initial_operations();
  int creates = 0;
  std::set<int64_t> first_lengths;
  std::map<std::string, int64_t> first_len;
  for (const auto& op : ops) {
    if (op.kind == SearcherOp::Kind::Create) ++creates;
    if (op.kind == SearcherOp::Kind::ValidateAfter &&
        !first_len.count(op.request_id)) {
      first_len[op.request_id] = op.length;
      first_lengths.insert(op.length);
    }
  }
  CHECK(creates >= 2);
  // multiple brackets → different rung-0 lengths
  CHECK(first_lengths.size() >= 2);
}

static void test_grid_searcher_runs_all_points() {
  Json cfg = Json::parse(
      "{\"name\": \"grid\", \"metric\": \"loss\", "
      "\"max_length\": {\"batches\": 4}}");
  Json spec = Json::parse(R"({
    "lr": {"type": "double", "minval": 0.0, "maxval": 1.0, "count": 2},
    "act": {"type": "categorical", "vals": ["a", "b"]}
  })");
  det::Searcher s(cfg, spec, 3);
  auto ops = s.initial_operations();
  int creates = 0;
  for (const auto& op : ops) {
    if (op.kind == SearcherOp::Kind::Create) ++creates;
  }
  CHECK_EQ(creates, 4);
}

// ----------------------------------------------------------- scheduler

static det::HostFreeView host(const std::string& id, int total,
                              std::vector<int> free) {
  det::HostFreeView v;
  v.id = id;
  v.total_slots = total;
  v.free_slots = std::move(free);
  return v;
}

static void test_fit_prefers_aligned_contiguous() {
  // host-a has a fragmented set; host-b has an aligned contiguous run.
  auto picks = det::find_fit(
      2, {host("a", 4, {1, 3}), host("b", 4, {2, 3})});
  CHECK_EQ(picks.size(), static_cast<size_t>(1));
  CHECK_EQ(picks[0].first, static_cast<size_t>(1));
  CHECK((picks[0].second == std::vector<int>{2, 3}));
}

static void test_fit_best_fit_least_leftover() {
  // both have aligned runs; prefer the fuller host (least leftover).
  auto picks = det::find_fit(
      2, {host("a", 8, {0, 1, 2, 3, 4, 5}), host("b", 4, {0, 1})});
  CHECK_EQ(picks.size(), static_cast<size_t>(1));
  CHECK_EQ(picks[0].first, static_cast<size_t>(1));
}

static void test_fit_multihost_uniform() {
  // need 8 over whole hosts: two free 4-slot hosts win; the fragmented
  // 8-slot host (not fully free) cannot join.
  auto picks = det::find_fit(
      8, {host("big", 8, {0, 1, 2, 3, 4, 5, 6}),  // one slot busy
          host("w1", 4, {0, 1, 2, 3}), host("w2", 4, {0, 1, 2, 3})});
  CHECK_EQ(picks.size(), static_cast<size_t>(2));
  CHECK_EQ(picks[0].first, static_cast<size_t>(1));
  CHECK_EQ(picks[1].first, static_cast<size_t>(2));
}

static void test_fit_multihost_heterogeneous_groups() {
  // r2 hardening case: hosts of different sizes — group by size; the
  // 8-slot pair divides 16 exactly, the lone 4-slot host is skipped.
  auto picks = det::find_fit(
      16, {host("s4", 4, {0, 1, 2, 3}), host("b1", 8, {0, 1, 2, 3, 4, 5, 6, 7}),
           host("b2", 8, {0, 1, 2, 3, 4, 5, 6, 7})});
  CHECK_EQ(picks.size(), static_cast<size_t>(2));
  std::set<size_t> idx{picks[0].first, picks[1].first};
  CHECK(idx == (std::set<size_t>{1, 2}));
}

static void test_fit_no_fit() {
  CHECK(det::find_fit(4, {host("a", 2, {0, 1})}).empty());
  CHECK(det::find_fit(1, {}).empty());
  // 3 doesn't divide into 2-slot whole hosts
  CHECK(det::find_fit(3, {host("a", 2, {0, 1}), host("b", 2, {0, 1})}).empty());
}

static void test_fit_zero_slot_aux() {
  auto picks = det::find_fit(0, {host("z", 2, {})});
  CHECK_EQ(picks.size(), static_cast<size_t>(1));
  CHECK(picks[0].second.empty());
}

static void test_round_robin_order() {
  // Groups take turns, one per round; within a group, submit order holds
  // (reference rm/agentrm/round_robin.go).
  using V = std::vector<size_t>;
  // items: A A A B B C (indices 0..5), cursor 0 → A B C A B A
  CHECK(det::round_robin_order({7, 7, 7, 8, 8, 9}, 0) ==
        (V{0, 3, 5, 1, 4, 2}));
  // cursor 1 rotates the starting group: B C A B A A
  CHECK(det::round_robin_order({7, 7, 7, 8, 8, 9}, 1) ==
        (V{3, 5, 0, 4, 1, 2}));
  // cursor wraps (and negative cursors behave)
  CHECK(det::round_robin_order({7, 8}, 2) == (V{0, 1}));
  CHECK(det::round_robin_order({7, 8}, -1) == (V{1, 0}));
  // single group / empty input
  CHECK(det::round_robin_order({5, 5, 5}, 3) == (V{0, 1, 2}));
  CHECK(det::round_robin_order({}, 0).empty());
  // interleaved submit order: A B A B keeps per-group order
  CHECK(det::round_robin_order({1, 2, 1, 2}, 0) == (V{0, 1, 2, 3}));
}

// ----------------------------------------------------------- preflight

static Json preflight_base_config() {
  Json cfg = Json::object();
  cfg["entrypoint"] = "python3 train.py";
  Json searcher = Json::object();
  searcher["name"] = "single";
  searcher["metric"] = "loss";
  Json ml = Json::object();
  ml["batches"] = static_cast<int64_t>(64);
  searcher["max_length"] = ml;
  cfg["searcher"] = searcher;
  cfg["hyperparameters"] = Json::object();
  Json res = Json::object();
  res["slots_per_trial"] = static_cast<int64_t>(8);
  cfg["resources"] = res;
  return cfg;
}

static void test_preflight_batch_mesh() {
  // 8 slots, default mesh (pure DP) -> batch axes product 8.
  Json cfg = preflight_base_config();
  cfg["hyperparameters"]["global_batch_size"] = static_cast<int64_t>(30);
  Json d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL201");
  CHECK_EQ(d.as_array()[0]["level"].as_string(), "error");

  // Divisible: clean.
  cfg["hyperparameters"]["global_batch_size"] = static_cast<int64_t>(32);
  CHECK(det::preflight_config(cfg).as_array().empty());

  // Explicit mesh: data=2 x fsdp=2 x tensor=2 -> batch axes product 4.
  Json mesh = Json::object();
  mesh["data"] = static_cast<int64_t>(2);
  mesh["fsdp"] = static_cast<int64_t>(2);
  mesh["tensor"] = static_cast<int64_t>(2);
  cfg["hyperparameters"]["mesh"] = mesh;
  cfg["hyperparameters"]["global_batch_size"] = static_cast<int64_t>(6);
  Json d2 = det::preflight_config(cfg);
  CHECK_EQ(d2.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d2.as_array()[0]["code"].as_string(), "DTL201");
  cfg["hyperparameters"]["global_batch_size"] = static_cast<int64_t>(8);
  CHECK(det::preflight_config(cfg).as_array().empty());

  // Unresolvable mesh (product mismatch) -> no DTL201 (schema layer's job).
  mesh["tensor"] = static_cast<int64_t>(3);
  cfg["hyperparameters"]["mesh"] = mesh;
  cfg["hyperparameters"]["global_batch_size"] = static_cast<int64_t>(7);
  CHECK(det::preflight_config(cfg).as_array().empty());

  // const-hparam spec form {type: const, val: N} is unwrapped.
  Json cfg2 = preflight_base_config();
  Json spec = Json::object();
  spec["type"] = "const";
  spec["val"] = static_cast<int64_t>(30);
  cfg2["hyperparameters"]["global_batch_size"] = spec;
  Json d3 = det::preflight_config(cfg2);
  CHECK_EQ(d3.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d3.as_array()[0]["code"].as_string(), "DTL201");
}

static void test_preflight_searcher_rungs() {
  Json cfg = preflight_base_config();
  cfg["searcher"]["name"] = "async_halving";
  cfg["searcher"]["num_rungs"] = static_cast<int64_t>(5);
  cfg["searcher"]["divisor"] = static_cast<int64_t>(4);
  Json ml = Json::object();
  ml["batches"] = static_cast<int64_t>(100);  // 100 < 4^4=256
  cfg["searcher"]["max_length"] = ml;
  Json d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL202");

  ml["batches"] = static_cast<int64_t>(256);  // exactly enough
  cfg["searcher"]["max_length"] = ml;
  CHECK(det::preflight_config(cfg).as_array().empty());
}

static void test_preflight_restarts_without_checkpoints() {
  Json cfg = preflight_base_config();
  // Explicit zero period + restarts (default max_restarts=5) -> DTL203.
  Json mcp = Json::object();
  mcp["batches"] = static_cast<int64_t>(0);
  cfg["min_checkpoint_period"] = mcp;
  Json d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL203");
  CHECK_EQ(d.as_array()[0]["level"].as_string(), "warning");

  // restarts off -> moot.
  cfg["max_restarts"] = static_cast<int64_t>(0);
  CHECK(det::preflight_config(cfg).as_array().empty());

  // periodic checkpoints -> clean.
  cfg["max_restarts"] = static_cast<int64_t>(3);
  mcp["batches"] = static_cast<int64_t>(50);
  cfg["min_checkpoint_period"] = mcp;
  CHECK(det::preflight_config(cfg).as_array().empty());

  // absent key (the default is also 0) must NOT fire.
  Json clean = preflight_base_config();
  clean["max_restarts"] = static_cast<int64_t>(3);
  CHECK(det::preflight_config(clean).as_array().empty());
}

static void test_preflight_elastic_sizes() {
  // 8 slots, elastic [2, 8], pure DP mesh: batch 32 divides 2,4,8 but
  // not 3,5,6,7 -> one DTL204 per bad size.
  Json cfg = preflight_base_config();
  cfg["hyperparameters"]["global_batch_size"] = static_cast<int64_t>(32);
  Json el = Json::object();
  el["min_slots"] = static_cast<int64_t>(2);
  el["max_slots"] = static_cast<int64_t>(8);
  cfg["resources"]["elastic"] = el;
  Json d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(4));
  for (const auto& diag : d.as_array()) {
    CHECK_EQ(diag["code"].as_string(), "DTL204");
    CHECK_EQ(diag["level"].as_string(), "error");
  }

  // tensor=2 must divide every size: 5 is unresolvable, and 6 resolves
  // to data=3 which 32 doesn't divide — one DTL204 each; 4 is clean.
  Json mesh = Json::object();
  mesh["tensor"] = static_cast<int64_t>(2);
  mesh["data"] = static_cast<int64_t>(-1);
  cfg["hyperparameters"]["mesh"] = mesh;
  el["min_slots"] = static_cast<int64_t>(4);
  el["max_slots"] = static_cast<int64_t>(6);
  cfg["resources"]["elastic"] = el;
  Json d2 = det::preflight_config(cfg);
  CHECK_EQ(d2.as_array().size(), static_cast<size_t>(2));
  CHECK_EQ(d2.as_array()[0]["code"].as_string(), "DTL204");
  CHECK_EQ(d2.as_array()[1]["code"].as_string(), "DTL204");

  // Divisor range: clean. Non-elastic: DTL204 never fires.
  el["min_slots"] = static_cast<int64_t>(4);
  el["max_slots"] = static_cast<int64_t>(8);
  cfg["resources"]["elastic"] = el;
  // sizes 4..8 with tensor=2: 5 and 7 unresolvable -> restrict to the
  // resolvable/divisible shape instead.
  el["min_slots"] = static_cast<int64_t>(8);
  el["max_slots"] = static_cast<int64_t>(8);
  cfg["resources"]["elastic"] = el;
  CHECK(det::preflight_config(cfg).as_array().empty());
  Json plain = preflight_base_config();
  plain["hyperparameters"]["global_batch_size"] = static_cast<int64_t>(32);
  CHECK(det::preflight_config(plain).as_array().empty());

  // Suppressible like every rule.
  el["min_slots"] = static_cast<int64_t>(2);
  el["max_slots"] = static_cast<int64_t>(8);
  cfg["resources"]["elastic"] = el;
  Json hp = Json::object();
  hp["global_batch_size"] = static_cast<int64_t>(32);
  cfg["hyperparameters"] = hp;  // drop the mesh block
  Json pf = Json::object();
  Json sup = Json::array();
  sup.push_back(Json("DTL204"));
  pf["suppress"] = sup;
  pf["gate"] = "error";
  cfg["preflight"] = pf;
  Json d3 = det::preflight_config(cfg);
  for (const auto& diag : d3.as_array()) {
    CHECK(diag["suppressed"].as_bool(false));
  }
  CHECK(!det::preflight_should_fail(cfg, d3));
}

static void test_preflight_shape_sweep() {
  // random searcher sampling global_batch_size raw over [16, 256] with
  // 32 trials -> far more distinct executables than the default 8.
  Json cfg = preflight_base_config();
  cfg["searcher"]["name"] = "random";
  cfg["searcher"]["max_trials"] = static_cast<int64_t>(32);
  Json gbs = Json::object();
  gbs["type"] = "int";
  gbs["minval"] = static_cast<int64_t>(16);
  gbs["maxval"] = static_cast<int64_t>(256);
  cfg["hyperparameters"]["global_batch_size"] = gbs;
  Json d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL205");
  CHECK_EQ(d.as_array()[0]["level"].as_string(), "warning");

  // Bucketing on: [16,256] maps to 5 buckets {16,32,64,128,256} <= 8.
  Json cc = Json::object();
  cc["bucket_batch_sizes"] = true;
  cfg["compile"] = cc;
  CHECK(det::preflight_config(cfg).as_array().empty());

  // Raised ceiling silences it too.
  cfg["compile"] = Json::object();
  cfg["compile"]["max_executables"] = static_cast<int64_t>(512);
  CHECK(det::preflight_config(cfg).as_array().empty());

  // single searcher: one trial, one executable — silent regardless.
  cfg["compile"] = Json();
  cfg["searcher"]["name"] = "single";
  CHECK(det::preflight_config(cfg).as_array().empty());

  // Non-shape sweep (lr) alone never fires.
  Json cfg2 = preflight_base_config();
  cfg2["searcher"]["name"] = "random";
  cfg2["searcher"]["max_trials"] = static_cast<int64_t>(32);
  Json lr = Json::object();
  lr["type"] = "log";
  lr["minval"] = static_cast<int64_t>(-4);
  lr["maxval"] = static_cast<int64_t>(-1);
  cfg2["hyperparameters"]["lr"] = lr;
  CHECK(det::preflight_config(cfg2).as_array().empty());

  // max_trials bounds the estimate: 4 trials can't exceed 8 executables.
  cfg["searcher"]["name"] = "random";
  cfg["searcher"]["max_trials"] = static_cast<int64_t>(4);
  CHECK(det::preflight_config(cfg).as_array().empty());

  // Config-level suppression works like every DTL2xx rule.
  cfg["searcher"]["max_trials"] = static_cast<int64_t>(32);
  Json sup = Json::object();
  Json codes = Json::array();
  codes.push_back(Json(std::string("DTL205")));
  sup["suppress"] = codes;
  cfg["preflight"] = sup;
  Json d3 = det::preflight_config(cfg);
  CHECK_EQ(d3.as_array().size(), static_cast<size_t>(1));
  CHECK(d3.as_array()[0]["suppressed"].as_bool(false));
}

static void test_preflight_capacity_knobs() {
  // DTL207 — capacity-loop knobs (native mirror of the Python expconf
  // checks; docs/cluster-ops.md "Capacity loop").
  auto cfg_with = [](int64_t mn, int64_t mx) {
    Json cfg = Json::object();
    Json serving = Json::object();
    Json rep = Json::object();
    rep["min"] = mn;
    rep["max"] = mx;
    serving["replicas"] = rep;
    cfg["serving"] = serving;
    return cfg;
  };
  // Scale-to-zero is legal: min 0, max 2 -> clean.
  CHECK(det::preflight_config(cfg_with(0, 2)).as_array().empty());
  // Negative min -> DTL207 error.
  Json d = det::preflight_config(cfg_with(-1, 2));
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL207");
  CHECK_EQ(d.as_array()[0]["level"].as_string(), "error");
  // min > max -> DTL207.
  d = det::preflight_config(cfg_with(3, 2));
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL207");
  // Floor above max -> DTL207; within -> clean.
  Json cfg = cfg_with(0, 2);
  cfg["serving"]["replicas"]["on_demand_floor"] = static_cast<int64_t>(3);
  d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL207");
  cfg["serving"]["replicas"]["on_demand_floor"] = static_cast<int64_t>(1);
  CHECK(det::preflight_config(cfg).as_array().empty());
  // Non-positive cold-start budget -> DTL207; positive -> clean.
  cfg["serving"]["replicas"]["cold_start_budget_s"] = 0.0;
  d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL207");
  cfg["serving"]["replicas"]["cold_start_budget_s"] = 30.0;
  CHECK(det::preflight_config(cfg).as_array().empty());
}

static void test_preflight_canary_fraction() {
  // DTL208 — canary traffic fraction (native mirror of
  // analysis/config_rules.py; docs/serving.md "Model lifecycle").
  auto cfg_with = [](Json fraction) {
    Json cfg = Json::object();
    Json serving = Json::object();
    Json canary = Json::object();
    canary["model"] = "m";
    if (!fraction.is_null()) canary["fraction"] = fraction;
    serving["canary"] = canary;
    serving["checkpoint"] = "latest";
    cfg["serving"] = serving;
    return cfg;
  };
  // A real fraction is clean.
  CHECK(det::preflight_config(cfg_with(Json(0.05))).as_array().empty());
  CHECK(det::preflight_config(cfg_with(Json(0.999))).as_array().empty());
  // Omitted fraction: the create path defaults it — clean.
  CHECK(det::preflight_config(cfg_with(Json())).as_array().empty());
  // 0, 1, negative, and non-numeric all fire DTL208 errors.
  for (const Json& bad :
       {Json(0.0), Json(1.0), Json(-0.2), Json(static_cast<int64_t>(2)),
        Json(std::string("lots"))}) {
    Json d = det::preflight_config(cfg_with(bad));
    CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
    CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL208");
    CHECK_EQ(d.as_array()[0]["level"].as_string(), "error");
  }
  // No canary block: never fires.
  Json cfg = Json::object();
  Json serving = Json::object();
  serving["checkpoint"] = "latest";
  cfg["serving"] = serving;
  CHECK(det::preflight_config(cfg).as_array().empty());
  // Suppressible like every DTL2xx rule.
  Json bad = cfg_with(Json(0.0));
  Json sup = Json::object();
  Json codes = Json::array();
  codes.push_back(Json(std::string("DTL208")));
  sup["suppress"] = codes;
  bad["preflight"] = sup;
  Json d = det::preflight_config(bad);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK(d.as_array()[0]["suppressed"].as_bool(false));
}

static void test_preflight_serving_kv_geometry() {
  // Serving config, block size does not divide max_seq -> DTL206 error.
  Json cfg = Json::object();
  Json serving = Json::object();
  serving["checkpoint"] = "latest";
  serving["kv_block_size"] = static_cast<int64_t>(24);
  serving["max_seq_len"] = static_cast<int64_t>(256);
  cfg["serving"] = serving;
  Json d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL206");
  CHECK_EQ(d.as_array()[0]["level"].as_string(), "error");

  // Divides -> clean; too-small explicit pool -> DTL206.
  cfg["serving"]["kv_block_size"] = static_cast<int64_t>(16);
  CHECK(det::preflight_config(cfg).as_array().empty());
  cfg["serving"]["kv_num_blocks"] = static_cast<int64_t>(8);  // 128 < 256
  d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL206");

  // Enough blocks -> clean. No attention_impl exempts a geometry: the
  // refused spelling of the deleted slot-dense layout fires like any.
  cfg["serving"]["kv_num_blocks"] = static_cast<int64_t>(16);  // 256
  CHECK(det::preflight_config(cfg).as_array().empty());
  cfg["serving"]["kv_num_blocks"] = static_cast<int64_t>(8);
  cfg["serving"]["kv_block_size"] = static_cast<int64_t>(24);
  cfg["serving"]["attention_impl"] = "dense";
  d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK_EQ(d.as_array()[0]["code"].as_string(), "DTL206");

  // Defaults (no explicit keys) never fire: 16 divides 256.
  Json clean = Json::object();
  clean["serving"] = Json::object();
  CHECK(det::preflight_config(clean).as_array().empty());

  // Suppressible like every rule.
  cfg["serving"]["attention_impl"] = "auto";
  Json pf = Json::object();
  pf["gate"] = "error";
  Json sup = Json::array();
  sup.push_back(Json("DTL206"));
  pf["suppress"] = sup;
  cfg["preflight"] = pf;
  d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK(d.as_array()[0]["suppressed"].as_bool(false));
  CHECK(!det::preflight_should_fail(cfg, d));
}

static void test_preflight_suppress_and_gate() {
  Json cfg = preflight_base_config();
  cfg["hyperparameters"]["global_batch_size"] = static_cast<int64_t>(30);

  // Default gate (warn): diagnostics never block.
  Json d = det::preflight_config(cfg);
  CHECK(!det::preflight_should_fail(cfg, d));

  // gate: error -> unsuppressed error blocks.
  Json pf = Json::object();
  pf["gate"] = "error";
  cfg["preflight"] = pf;
  d = det::preflight_config(cfg);
  CHECK(det::preflight_should_fail(cfg, d));

  // Suppressed code is marked and no longer blocks.
  Json sup = Json::array();
  sup.push_back(Json("DTL201"));
  cfg["preflight"]["suppress"] = sup;
  d = det::preflight_config(cfg);
  CHECK_EQ(d.as_array().size(), static_cast<size_t>(1));
  CHECK(d.as_array()[0]["suppressed"].as_bool(false));
  CHECK(!det::preflight_should_fail(cfg, d));
}

// ---------------------------------------------------- reconnect backoff

static void test_backoff_jitter_bounds_and_spread() {
  // Equal jitter: every delay lands in [ceiling/2, ceiling) where the
  // ceiling doubles per attempt and caps at cap_s.
  for (int attempt = 0; attempt < 10; ++attempt) {
    double ceiling = std::min(30.0, 1.0 * (1 << std::min(attempt, 5)));
    for (unsigned s = 1; s <= 20; ++s) {
      unsigned seed = s;
      double d = det::backoff::jittered_delay_s(attempt, &seed);
      CHECK(d >= ceiling / 2.0);
      CHECK(d < ceiling);
    }
  }
  // Thundering-herd spread: a fleet of agents seeded differently must not
  // retry in lockstep — distinct seeds yield many distinct delays.
  std::set<long> distinct;
  for (unsigned s = 1; s <= 50; ++s) {
    unsigned seed = s;
    distinct.insert(static_cast<long>(
        1e6 * det::backoff::jittered_delay_s(3, &seed)));
  }
  CHECK(distinct.size() >= 25);
  // The same seed advances across attempts (the caller reuses one seed),
  // so consecutive retries from one agent differ too.
  unsigned seed = 7;
  double d1 = det::backoff::jittered_delay_s(5, &seed);
  double d2 = det::backoff::jittered_delay_s(5, &seed);
  double d3 = det::backoff::jittered_delay_s(5, &seed);
  CHECK(d1 != d2 || d2 != d3);
  // Cap holds far past the doubling range, and the base/cap knobs bite.
  unsigned seed2 = 3;
  CHECK(det::backoff::jittered_delay_s(1000, &seed2) < 30.0);
  unsigned seed3 = 3;
  double capped = det::backoff::jittered_delay_s(1000, &seed3, 1.0, 10.0);
  CHECK(capped >= 5.0);
  CHECK(capped < 10.0);
}

// ---------------------------------------------------------- fault points

static void test_faultpoint_catalogue_and_counted_arm() {
  // Regression: the master fired master.resize.offer.drop and
  // provisioner.create.fail but the kKnown catalogue didn't list them
  // (surfaced by the NL004 registry lint) — the debug route could not
  // discover them, and docs/chaos.md drifted. Every fired point must be
  // listable.
  Json listed = det::faults::list();
  std::set<std::string> names;
  for (const auto& p : listed["points"].as_array())
    names.insert(p["name"].as_string());
  CHECK(names.count("master.resize.offer.drop") == 1);
  CHECK(names.count("provisioner.create.fail") == 1);

  // Counted arm through the public API: fires exactly `count` times,
  // then auto-disarms back to the no-op fast path.
  std::string err;
  CHECK(det::faults::arm("provisioner.create.fail", "error", 2, 0.0, &err));
  CHECK(err.empty());
  CHECK(det::faults::any_armed());
  CHECK(FAULT_POINT("provisioner.create.fail") ==
        det::faults::Action::kError);
  CHECK(FAULT_POINT("provisioner.create.fail") ==
        det::faults::Action::kError);
  CHECK(FAULT_POINT("provisioner.create.fail") ==
        det::faults::Action::kNone);
  // A malformed mode is rejected, not silently armed.
  CHECK(!det::faults::arm("provisioner.create.fail", "explode", 0, 0.0,
                          &err));
  CHECK(!err.empty());
  det::faults::disarm_all();
  CHECK(!det::faults::any_armed());
}

// -------------------------------------------------------------- driver

int main() {
  struct Test {
    const char* name;
    std::function<void()> fn;
  };
  std::vector<Test> tests = {
      {"json_roundtrip", test_json_roundtrip},
      {"json_malformed", test_json_malformed},
      {"json_defaults", test_json_defaults},
      {"sample_hparams", test_sample_hparams},
      {"grid_points", test_grid_points},
      {"single_searcher", test_single_searcher},
      {"asha_promote_semantics", test_asha_promote_semantics},
      {"asha_snapshot_restore", test_asha_snapshot_restore_determinism},
      {"adaptive_asha_brackets", test_adaptive_asha_brackets},
      {"grid_searcher_all_points", test_grid_searcher_runs_all_points},
      {"fit_aligned_contiguous", test_fit_prefers_aligned_contiguous},
      {"fit_best_fit", test_fit_best_fit_least_leftover},
      {"fit_multihost_uniform", test_fit_multihost_uniform},
      {"fit_multihost_heterogeneous", test_fit_multihost_heterogeneous_groups},
      {"fit_no_fit", test_fit_no_fit},
      {"fit_zero_slot_aux", test_fit_zero_slot_aux},
      {"round_robin_order", test_round_robin_order},
      {"preflight_batch_mesh", test_preflight_batch_mesh},
      {"preflight_elastic_sizes", test_preflight_elastic_sizes},
      {"preflight_searcher_rungs", test_preflight_searcher_rungs},
      {"preflight_restarts_without_checkpoints",
       test_preflight_restarts_without_checkpoints},
      {"preflight_shape_sweep", test_preflight_shape_sweep},
      {"preflight_serving_kv_geometry", test_preflight_serving_kv_geometry},
      {"preflight_capacity_knobs", test_preflight_capacity_knobs},
      {"preflight_canary_fraction", test_preflight_canary_fraction},
      {"preflight_suppress_and_gate", test_preflight_suppress_and_gate},
      {"backoff_jitter", test_backoff_jitter_bounds_and_spread},
      {"faultpoint_catalogue", test_faultpoint_catalogue_and_counted_arm},
  };
  for (auto& t : tests) {
    int before = g_failures;
    t.fn();
    std::printf("%-32s %s\n", t.name,
                g_failures == before ? "ok" : "FAILED");
  }
  std::printf("%d checks, %d failures\n", g_checks, g_failures);
  return g_failures == 0 ? 0 : 1;
}
