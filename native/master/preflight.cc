// Native preflight (see preflight.h). Mirrors
// determined_tpu/analysis/config_rules.py rule-for-rule; if the two ever
// disagree, the Python analyzer is the source of truth and this file is
// the bug.

#include "preflight.h"

#include <algorithm>
#include <cmath>
#include <set>
#include <string>
#include <utility>
#include <vector>

namespace det {

namespace {

const char* kAxisOrder[] = {"data",   "pipeline", "fsdp",
                            "expert", "context",  "tensor"};

Json diag(const char* code, const char* level, const std::string& msg) {
  Json d = Json::object();
  d["code"] = code;
  d["level"] = level;
  d["message"] = msg;
  d["engine"] = "config";
  return d;
}

// data*fsdp resolved against `slots` (default: slots_per_trial), mirroring
// MeshConfig.resolve (omitted `data` = -1 absorbs remaining chips).
// 0 = unresolvable (schema validation reports that separately). DTL204
// re-resolves at every elastic candidate size via the override.
int64_t batch_axes_product(const Json& config, int64_t slots = -1) {
  const Json& mesh = config["hyperparameters"]["mesh"];
  if (slots < 0) slots = config["resources"]["slots_per_trial"].as_int(1);
  if (slots <= 0) return 0;
  if (!mesh.is_object()) {
    // No mesh block: MeshConfig() defaults to pure data parallel over all
    // chips -> batch axes product == slots.
    return slots;
  }
  std::map<std::string, int64_t> sizes;
  for (const char* a : kAxisOrder) sizes[a] = 1;
  std::vector<std::string> unknown;
  for (const auto& [axis, v] : mesh.as_object()) {
    if (sizes.find(axis) == sizes.end() || !v.is_int()) return 0;
    int64_t s = v.as_int();
    if (s == -1) {
      unknown.push_back(axis);
    } else if (s > 0) {
      sizes[axis] = s;
    } else {
      return 0;
    }
  }
  if (mesh["data"].is_null()) unknown.push_back("data");
  if (unknown.size() > 1) return 0;
  int64_t fixed = 1;
  for (const char* a : kAxisOrder) {
    bool is_unknown = !unknown.empty() && unknown[0] == a;
    if (!is_unknown) fixed *= sizes[a];
  }
  if (!unknown.empty()) {
    if (fixed == 0 || slots % fixed != 0) return 0;
    sizes[unknown[0]] = slots / fixed;
  } else if (fixed != slots) {
    return 0;
  }
  return sizes["data"] * sizes["fsdp"];
}

// DTL205 helpers — mirror determined_tpu/analysis/config_rules.py
// (SHAPE_HPARAM_TOKENS / _spec_distinct) token for token.
const std::set<std::string>& shape_tokens() {
  static const std::set<std::string> kTokens = {
      "batch",    "size",      "dim",     "dims",    "width",   "depth",
      "layer",    "layers",    "head",    "heads",   "seq",     "len",
      "length",   "vocab",     "position", "positions", "expert",
      "experts",  "hidden",    "model",   "feature", "features",
      "channel",  "channels",  "embed",   "embedding"};
  return kTokens;
}

bool is_shape_hparam(const std::string& name) {
  std::string tok;
  for (size_t i = 0; i <= name.size(); ++i) {
    if (i == name.size() || name[i] == '_') {
      std::string lower = tok;
      for (auto& c : lower) c = static_cast<char>(tolower(c));
      if (shape_tokens().count(lower)) return true;
      tok.clear();
    } else {
      tok.push_back(name[i]);
    }
  }
  return false;
}

int64_t bucket_boundary(int64_t n, const Json& buckets) {
  if (n <= 0) return n;
  if (buckets.is_array() && !buckets.as_array().empty()) {
    std::vector<int64_t> bs;
    for (const auto& b : buckets.as_array()) {
      if (b.is_int()) bs.push_back(b.as_int());
    }
    std::sort(bs.begin(), bs.end());
    for (int64_t b : bs) {
      if (b >= n) return b;
    }
    return n;
  }
  int64_t p = 1;
  while (p < n) p <<= 1;
  return p;
}

constexpr int64_t kUnbounded = 1000000000;

int64_t distinct_bucketed_batches(int64_t mn, int64_t mx,
                                  const Json& buckets) {
  int64_t n = 0, b = mn;
  while (b <= mx && n <= 64) {
    ++n;
    int64_t bb = bucket_boundary(b, buckets);
    b = (bb > b ? bb : b) + 1;
  }
  return n > 0 ? n : 1;
}

// (distinct shapes, bucketing applied) for one hparam spec.
std::pair<int64_t, bool> spec_distinct(const std::string& name,
                                       const Json& spec, bool bucket_on,
                                       const Json& buckets) {
  if (!spec.is_object() || !spec["type"].is_string()) return {1, false};
  const std::string t = spec["type"].as_string("");
  const bool is_gbs = name == "global_batch_size";
  if (t == "const") return {1, false};
  if (t == "categorical") {
    const auto& vals = spec["vals"].as_array();
    if (is_gbs && bucket_on) {
      std::set<int64_t> bs;
      for (const auto& v : vals) {
        if (v.is_int()) bs.insert(bucket_boundary(v.as_int(), buckets));
      }
      if (!bs.empty()) return {static_cast<int64_t>(bs.size()), true};
    }
    return {std::max<int64_t>(1, static_cast<int64_t>(vals.size())), false};
  }
  if (t == "int") {
    if (!spec["minval"].is_int() || !spec["maxval"].is_int()) return {1, false};
    int64_t mn = spec["minval"].as_int(), mx = spec["maxval"].as_int();
    if (mx < mn) return {1, false};
    if (is_gbs && bucket_on) {
      return {distinct_bucketed_batches(mn, mx, buckets), true};
    }
    int64_t cnt = spec["count"].as_int(0);
    if (cnt > 0) return {std::min(cnt, mx - mn + 1), false};
    return {mx - mn + 1, false};
  }
  // double/log
  int64_t cnt = spec["count"].as_int(0);
  if (cnt > 0) return {cnt, false};
  return {kUnbounded, false};
}

int64_t length_batches(const Json& v) {
  if (v.is_number()) return v.as_int();
  if (v.is_object()) {
    for (const char* unit : {"batches", "records", "epochs"}) {
      if (!v[unit].is_null()) return v[unit].as_int();
    }
  }
  return 0;
}

}  // namespace

Json preflight_config(const Json& config) {
  Json out = Json::array();
  if (!config.is_object()) return out;

  // DTL201 — global_batch_size vs mesh batch axes.
  Json gbs_node = config["hyperparameters"]["global_batch_size"];
  if (gbs_node.is_object() &&
      gbs_node["type"].as_string("") == "const") {
    gbs_node = gbs_node["val"];
  }
  int64_t gbs = gbs_node.is_int() ? gbs_node.as_int() : 0;
  if (gbs > 0) {
    int64_t bprod = batch_axes_product(config);
    if (bprod > 1 && gbs % bprod != 0) {
      out.push_back(diag(
          "DTL201", "error",
          "hyperparameters.global_batch_size=" + std::to_string(gbs) +
              " is not divisible by the mesh batch axes data x fsdp = " +
              std::to_string(bprod) +
              " (resolved against resources.slots_per_trial=" +
              std::to_string(
                  config["resources"]["slots_per_trial"].as_int(1)) +
              ")"));
    }
  }

  // DTL202 — ASHA budget vs rungs.
  const Json& searcher = config["searcher"];
  const std::string name = searcher["name"].as_string("");
  if (name == "async_halving" || name == "sync_halving") {
    int64_t max_length = length_batches(searcher["max_length"]);
    int64_t num_rungs = searcher["num_rungs"].as_int(0);
    double divisor = searcher["divisor"].as_double(4.0);
    if (max_length > 0 && num_rungs > 1 && divisor > 1.0) {
      double bottom =
          static_cast<double>(max_length) / std::pow(divisor, num_rungs - 1);
      if (bottom < 1.0) {
        out.push_back(diag(
            "DTL202", "error",
            "searcher.max_length=" + std::to_string(max_length) +
                " < divisor^(num_rungs-1)=" +
                std::to_string(static_cast<int64_t>(divisor)) + "^" +
                std::to_string(num_rungs - 1) + "=" +
                std::to_string(static_cast<int64_t>(
                    std::pow(divisor, num_rungs - 1))) +
                ": the bottom rung would train for zero batches and the "
                "top rungs are unreachable; lower num_rungs or raise "
                "max_length"));
      }
    }
  }

  // DTL204 — elastic configs must be runnable at EVERY slot count in
  // [min_slots, max_slots]: mesh resolvability + batch divisibility per
  // size (the Python analyzer also runs the abstract-trace HBM leg, which
  // needs the trial code the master never imports).
  const Json& elastic = config["resources"]["elastic"];
  if (elastic.is_object()) {
    int64_t spt = config["resources"]["slots_per_trial"].as_int(1);
    int64_t mn = elastic["min_slots"].as_int(1);
    int64_t mx = elastic["max_slots"].as_int(spt);
    if (mn >= 1 && mn <= mx) {
      for (int64_t k = mn; k <= mx; ++k) {
        int64_t bprod = batch_axes_product(config, k);
        if (bprod == 0) {
          out.push_back(diag(
              "DTL204", "error",
              "elastic size " + std::to_string(k) + " (of [" +
                  std::to_string(mn) + ", " + std::to_string(mx) +
                  "]): hyperparameters.mesh does not resolve at this slot "
                  "count — the fixed axes product must divide every size "
                  "the scheduler may shrink/grow the trial to"));
        } else if (gbs > 0 && gbs % bprod != 0) {
          out.push_back(diag(
              "DTL204", "error",
              "elastic size " + std::to_string(k) + " (of [" +
                  std::to_string(mn) + ", " + std::to_string(mx) +
                  "]): hyperparameters.global_batch_size=" +
                  std::to_string(gbs) +
                  " is not divisible by the mesh batch axes data x fsdp = " +
                  std::to_string(bprod) + " at this slot count"));
        }
      }
    }
  }

  // DTL205 — shape-affecting hparam sweep without bucketing
  // (docs/compile-farm.md): each distinct shape compiles its own
  // executable and the compile farm can't share across them.
  {
    const std::string sname = searcher["name"].as_string("");
    const Json& hp = config["hyperparameters"];
    if (!sname.empty() && sname != "single" && sname != "custom" &&
        hp.is_object()) {
      const Json& cc = config["compile"];
      bool bucket_on = cc.is_object() && cc["bucket_batch_sizes"].as_bool(false);
      const Json& buckets = cc["buckets"];
      int64_t max_exec =
          cc.is_object() ? cc["max_executables"].as_int(8) : 8;
      if (max_exec < 1) max_exec = 8;
      int64_t total = 1;
      bool bucketable = false;
      std::string offenders;
      for (const auto& [hname, spec] : hp.as_object()) {
        if (hname == "mesh" || !is_shape_hparam(hname)) continue;
        auto [n, bucketed] = spec_distinct(hname, spec, bucket_on, buckets);
        if (n > 1) {
          if (!offenders.empty()) offenders += ", ";
          offenders += hname + " (" +
                       (n >= kUnbounded ? std::string("unbounded")
                                        : std::to_string(n)) +
                       " distinct shapes)";
          total = std::min<int64_t>(total * n, kUnbounded);
          if (hname == "global_batch_size" && !bucketed) bucketable = true;
        }
      }
      int64_t max_trials = searcher["max_trials"].as_int(0);
      if (max_trials > 0) total = std::min(total, max_trials);
      if (!offenders.empty() && total > max_exec) {
        std::string hint =
            bucketable ? "enable compile.bucket_batch_sizes so batch sizes "
                         "share bucketed executables, "
                       : "";
        out.push_back(diag(
            "DTL205", "warning",
            "searcher sweep implies ~" +
                (total >= kUnbounded ? std::string("unbounded")
                                     : std::to_string(total)) +
                " distinct executables from shape-affecting "
                "hyperparameters [" + offenders +
                "] > compile.max_executables=" + std::to_string(max_exec) +
                ": each distinct shape pays a full XLA compile and the "
                "compile farm cannot share artifacts across them; " + hint +
                "use const/categorical values, or raise "
                "compile.max_executables if intended"));
      }
    }
  }

  // DTL206 — serving paged-KV geometry (docs/serving.md "Paged KV &
  // prefix caching"): kv_block_size must divide max_seq_len, and an
  // explicit kv_num_blocks must hold at least one worst-case sequence.
  const Json& serving = config["serving"];
  if (serving.is_object()) {
    int64_t bs = serving["kv_block_size"].as_int(16);
    int64_t max_seq = serving["max_seq_len"].as_int(256);
    int64_t nb = serving["kv_num_blocks"].as_int(0);
    if (bs > 0 && max_seq > 0) {
      if (max_seq % bs != 0) {
        out.push_back(diag(
            "DTL206", "error",
            "serving.kv_block_size=" + std::to_string(bs) +
                " does not divide serving.max_seq_len=" +
                std::to_string(max_seq) +
                ": the paged block tables tile max_seq_len exactly; pick "
                "a block size that divides it"));
      } else if (nb > 0 && nb * bs < max_seq) {
        out.push_back(diag(
            "DTL206", "error",
            "serving.kv_num_blocks=" + std::to_string(nb) +
                " x kv_block_size=" + std::to_string(bs) + " = " +
                std::to_string(nb * bs) +
                " tokens of paged KV pool cannot hold even one "
                "max_seq_len=" + std::to_string(max_seq) +
                " sequence — no request could ever be admitted; raise "
                "kv_num_blocks or lower max_seq_len"));
      }
    }
    // DTL207 — capacity-loop knobs (docs/cluster-ops.md "Capacity
    // loop"): the native mirror of the Python expconf checks for
    // scale-to-zero and spot-floor configuration. The master is the
    // authority — a CLI that skipped client-side validation must still
    // be refused here.
    const Json& rep = serving["replicas"];
    if (rep.is_object()) {
      int64_t mn = rep["min"].as_int(1);
      int64_t tgt = rep["target"].as_int(mn);
      int64_t mx = rep["max"].as_int(
          std::max<int64_t>(1, std::max(mn, tgt)));
      if (mn < 0) {
        out.push_back(diag(
            "DTL207", "error",
            "serving.replicas.min=" + std::to_string(mn) +
                " is negative; 0 (scale-to-zero) is the smallest legal "
                "floor"));
      } else if (mn > mx) {
        out.push_back(diag(
            "DTL207", "error",
            "serving.replicas.min=" + std::to_string(mn) +
                " exceeds max=" + std::to_string(mx)));
      }
      // Default floor derives from min but is clamped to max so a
      // min>max config yields one finding, not a derived-floor echo.
      int64_t floor = rep["on_demand_floor"].as_int(
          std::min(std::max<int64_t>(mn, 0), mx));
      if (floor < 0 || floor > mx) {
        out.push_back(diag(
            "DTL207", "error",
            "serving.replicas.on_demand_floor=" + std::to_string(floor) +
                " must be within [0, max=" + std::to_string(mx) +
                "]: a floor above max can never be satisfied and would "
                "pin every replica to on-demand capacity"));
      }
      if (!rep["cold_start_budget_s"].is_null() &&
          rep["cold_start_budget_s"].as_double(0) <= 0) {
        out.push_back(diag(
            "DTL207", "error",
            "serving.replicas.cold_start_budget_s must be a positive "
            "number of seconds: it bounds how long the router holds a "
            "request while a scale-from-zero replica restores"));
      }
    }

    // DTL208 — canary traffic fraction (docs/serving.md "Model
    // lifecycle"): mirror of analysis/config_rules.py. A declared
    // serving.canary.fraction must sit strictly inside (0, 1); the
    // deployment-create gate refuses anything else.
    const Json& canary = serving["canary"];
    if (canary.is_object() && !canary["fraction"].is_null()) {
      double frac = canary["fraction"].is_number()
                        ? canary["fraction"].as_double()
                        : -1.0;
      if (!(frac > 0.0 && frac < 1.0)) {
        out.push_back(diag(
            "DTL208", "error",
            "serving.canary.fraction=" + canary["fraction"].dump() +
                " must be strictly inside (0, 1): 0 routes nothing to "
                "the canary and 1 is a full rollout — use `det serve "
                "update` for that"));
      }
    }
  }

  // DTL203 — restarts configured but nothing to restart from. Only an
  // EXPLICIT min_checkpoint_period: 0 fires (key present): the default is
  // also 0 batches and flagging every config would be pure noise.
  if (!config["min_checkpoint_period"].is_null()) {
    int64_t mcp = length_batches(config["min_checkpoint_period"]);
    int64_t mr = config["max_restarts"].as_int(5);
    if (mcp == 0 && mr > 0) {
      out.push_back(diag(
          "DTL203", "warning",
          "min_checkpoint_period: 0 with max_restarts=" +
              std::to_string(mr) +
              ": mid-op failures can only restart from the previous "
              "op-boundary checkpoint (or from scratch); set a periodic "
              "min_checkpoint_period or max_restarts: 0"));
    }
  }

  // Apply config-level suppressions (preflight.suppress: [DTLnnn, ...]).
  const Json& suppress = config["preflight"]["suppress"];
  if (suppress.is_array() && !suppress.as_array().empty()) {
    std::set<std::string> codes;
    for (const auto& c : suppress.as_array()) {
      if (c.is_string()) codes.insert(c.as_string());
    }
    for (auto& d : out.mutable_array()) {
      if (codes.count(d["code"].as_string())) {
        d["suppressed"] = true;
        d["suppressed_by"] = "config";
      }
    }
  }
  return out;
}

bool preflight_should_fail(const Json& config, const Json& diagnostics) {
  if (config["preflight"]["gate"].as_string("warn") != "error") return false;
  for (const auto& d : diagnostics.as_array()) {
    if (d["level"].as_string("") == "error" && !d["suppressed"].as_bool(false)) {
      return true;
    }
  }
  return false;
}

}  // namespace det
