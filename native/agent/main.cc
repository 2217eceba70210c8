// determined-agent — TPU-VM node daemon.
//
// Native analogue of the reference Go agent (agent/internal/agent.go:86
// run loop; device detection detect/detect.go:19; container lifecycle
// containers/manager.go + container/container.go). Differences, by design:
//  - transport is HTTP long-poll against the master instead of a websocket;
//  - tasks are host processes, not docker containers (a TPU-VM host runs
//    one process owning all local chips; the agent supervises it directly);
//  - slots are TPU chips detected from /dev/accel* or, where the host
//    exposes its chips through vfio-pci instead (v5e), /dev/vfio/<group>;
//    DET_AGENT_SLOTS is the "artificial slots" testing override
//    (detect.go:39-56).
//
// Log shipping follows master/static/srv/ship_logs.py: reader threads
// collect child stdout/stderr lines, a shipper thread batches them to
// POST /api/v1/task/logs.

#include <dirent.h>
#include <fcntl.h>
#include <signal.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <sys/wait.h>
#include <unistd.h>

#include <climits>

#include <atomic>
#include <condition_variable>
#include <cstring>
#include <deque>
#include <fstream>
#include <iostream>
#include <map>
#include <mutex>
#include <sstream>
#include <thread>
#include <vector>

#include "../common/faultpoint.h"
#include "../common/http.h"
#include "../common/json.h"
#include "../common/mutex.h"
#include "../common/trace.h"
#include "backoff.h"

namespace {

using det::HttpClientResponse;
using det::Json;
using det::JsonObject;

struct AgentOptions {
  std::string master_url = "http://127.0.0.1:8080";
  std::string id;
  std::string resource_pool = "default";
  std::string addr;  // host address peers can reach (rendezvous)
  std::string work_root = "/tmp/determined-agent";
  // Path to the master-minted bootstrap token (<db>.agent_token). The
  // service account is token-only; there is no password fallback.
  std::string token_file;
  // CA bundle for an https:// master (DET_MASTER_CERT_FILE analogue of
  // reference certs.py); empty = system roots.
  std::string master_cert_file;
  int slots_override = -1;  // DET_AGENT_SLOTS / --slots ("artificial")
  std::string slot_type = "auto";
  // Capacity class declared to the master at registration: a preemptible
  // (spot) node is reclaimable surplus — the scheduler keeps deployment
  // floors off it and places surplus serve replicas on it first
  // (docs/cluster-ops.md "Capacity loop"). Deploy tooling wires this from
  // the instance's schedulingConfig.
  bool preemptible = false;
  double poll_timeout_s = 20.0;
  // Ownership lease TTL (docs/cluster-ops.md "Leases, fencing &
  // split-brain"): if the agent cannot renew its lease against the master
  // for this long — a partition, from this side — it SELF-FENCES: kills
  // every local task before the master's reclaim deadline
  // (agent_timeout_s) hands their allocations to another node, so two
  // agents never run the same allocation concurrently. 0 (the default)
  // adopts the master's lease_ttl_s from register/heartbeat responses,
  // keeping both sides on one clock; an explicit value here PINS the TTL
  // against the master's — an ops/chaos override.
  double lease_ttl_s = 0;
  // Spot-capacity survival (docs/cluster-ops.md "Preemption & drain"):
  // grace the agent advertises when IT is told to terminate (SIGTERM),
  // and the pluggable termination-notice source. notice_source "gce"
  // polls the GCE metadata preemption/maintenance endpoints; notice_file
  // is a test/ops hook — when the file appears, its JSON
  // {deadline_seconds, reason} is the notice.
  double term_grace_s = 30.0;
  std::string notice_source;  // "" = off | "gce"
  std::string notice_file;
  std::string gce_metadata_url = "http://metadata.google.internal";
  // Node-local Prometheus endpoint (docs/observability.md): every agent
  // exposes its own /metrics so a fleet scrape sees task states, log-ship
  // backlog and drain state per node. 0 = disabled; -1 = ephemeral port
  // (printed at startup; tests use this).
  int metrics_port = 0;
};

struct Task {
  std::string allocation_id;
  std::string container_id;
  std::string task_id;
  std::string workdir;
  // Lifecycle tracing (docs/observability.md): trial db id + trace id
  // from the start action's env (DET_TRIAL_ID / DET_TRACE_ID); trial_id
  // <= 0 (NTSC tasks) emits no spans.
  long long trial_id = -1;
  std::string trace_id;
  pid_t pid = -1;        // the sh wrapper's pid (the task's process group)
  long long pid_start = 0;  // /proc/<pid>/stat starttime: adoption identity
                            // check against pid recycling
  int rank = 0;
  bool adopted = false;  // reattached after an agent restart: not our
                         // child, supervised by /proc polling
  std::atomic<bool> exited{false};
  // Exit code awaiting a CONFIRMED delivery to the master (INT_MIN =
  // none). Kept in the registry until delivered so a master outage — or
  // an agent death mid-retry — never loses an exit.
  std::atomic<int> pending_exit{INT_MIN};
  // Shipped-log offsets, persisted so a restarted agent resumes the tail
  // without dropping the downtime window (duplicates of up to one flush
  // interval are possible; the log-policy actions are idempotent).
  std::atomic<long> off_out{0}, off_err{0};
  // Tail threads that have finished their final drain (2 = both).
  // finish_task waits on this so logs are DURABLE before EXITED is
  // reported — `det task logs` on a just-finished task must see output.
  std::atomic<int> tails_done{0};
  // Whether supervise() actually spawned tails for this incarnation: the
  // reattach paths that find a task already dead never do, and must not
  // stall the drain waiting for threads that don't exist.
  bool tails_spawned = false;
};

det::Mutex g_mu;
// by container_id; the shared_ptr pins a Task across a supervise thread's
// lifetime — per-task mutable fields are atomics (Task definition above).
std::map<std::string, std::shared_ptr<Task>> g_tasks GUARDED_BY(g_mu);

// Observability state for /metrics (docs/observability.md).
std::atomic<bool> g_draining{false};  // termination notice posted
std::atomic<int> g_slots{0};          // slots registered with the master
std::atomic<bool> g_tpu_slots{false};  // ...and whether they are tpu chips
const auto g_started = std::chrono::steady_clock::now();

// Ownership-lease state (docs/cluster-ops.md "Leases, fencing &
// split-brain"). The lease is renewed by successful register/heartbeat
// round-trips ONLY — the action long-poll doesn't count, mirroring the
// master, so both sides judge the partition by the same channel.
std::atomic<double> g_lease_ttl{30.0};
std::atomic<bool> g_lease_ttl_pinned{false};  // explicit local config wins
std::atomic<long long> g_lease_renewed_us{0};       // steady clock, us
std::atomic<long long> g_lease_renewed_wall_us{0};  // wall clock, us (spans)
std::atomic<bool> g_self_fenced{false};

long long steady_us() {
  return std::chrono::duration_cast<std::chrono::microseconds>(
             std::chrono::steady_clock::now() - g_started)
      .count();
}

void renew_lease() {
  g_lease_renewed_us = steady_us();
  g_lease_renewed_wall_us = det::trace::now_us();
  g_self_fenced = false;
}

double lease_remaining_s() {
  long long renewed = g_lease_renewed_us.load();
  if (renewed == 0) return g_lease_ttl.load();  // never registered yet
  double elapsed = (steady_us() - renewed) / 1e6;
  return g_lease_ttl.load() - elapsed;
}

// SIGTERM is a termination notice, not an exit: the handler only raises a
// flag; the notice watcher turns it into a master notification and keeps
// the task-log drain alive through the grace window.
std::atomic<bool> g_sigterm{false};
void handle_sigterm(int) { g_sigterm.store(true); }

bool has_running_tasks() {
  det::MutexLock lock(g_mu);
  for (const auto& [cid, t] : g_tasks) {
    if (!t->exited) return true;
  }
  return false;
}

// ---- master session -----------------------------------------------------
// All master routes require a Bearer token; the agent logs in at startup
// (service account "determined-agent", or a pre-issued DET_AGENT_TOKEN) and
// re-logins transparently on 401 (e.g. after a master restart wiped
// sessions).

det::Mutex g_token_mu;
std::string g_token GUARDED_BY(g_token_mu);

std::map<std::string, std::string> auth_headers() {
  det::MutexLock lock(g_token_mu);
  if (g_token.empty()) return {};
  return {{"Authorization", "Bearer " + g_token}};
}

// not-guarded: written once by option parsing before any thread starts,
// read-only afterwards (agent_login re-reads the FILE, not this path).
std::string g_token_file;

bool agent_login(const std::string& master_url, bool use_env_token = true) {
  // The service account is token-only: DET_AGENT_TOKEN env, or the
  // master-minted token file (<db>.agent_token, shared via the node's
  // provisioning / deploy tooling). On the 401-recovery path
  // (use_env_token=false, e.g. after a master DB wipe) the token FILE is
  // re-read — the master rewrites it at boot — while a stale env token is
  // not re-installed.
  (void)master_url;
  if (use_env_token) {
    if (const char* t = getenv("DET_AGENT_TOKEN")) {
      det::MutexLock lock(g_token_mu);
      g_token = t;
      return true;
    }
  }
  if (!g_token_file.empty()) {
    std::ifstream f(g_token_file);
    std::string tok;
    if (f && std::getline(f, tok) && !tok.empty()) {
      det::MutexLock lock(g_token_mu);
      if (g_token == tok && !use_env_token) return false;  // already stale
      g_token = tok;
      return true;
    }
  }
  return false;
}

HttpClientResponse master_call(const std::string& master_url,
                               const std::string& method,
                               const std::string& path,
                               const std::string& body, double timeout_s) {
  auto r = det::http_request(method, master_url, path, body, timeout_s,
                             auth_headers());
  if (r.status == 401 && agent_login(master_url, /*use_env_token=*/false)) {
    r = det::http_request(method, master_url, path, body, timeout_s,
                          auth_headers());
  }
  return r;
}

// ---- log shipping -------------------------------------------------------

struct LogEntry {
  Json entry;
};
det::Mutex g_log_mu;
std::condition_variable g_log_cv;
std::deque<Json> g_log_queue GUARDED_BY(g_log_mu);
// Undelivered line count per task id (queued + in-flight). Exit reporting
// waits for THIS task's count to hit zero — completion implies logs
// durable, and an unrelated chatty task can't stall the drain.
std::map<std::string, long> g_log_pending GUARDED_BY(g_log_mu);
std::atomic<bool> g_running{true};

void enqueue_log(const std::string& task_id, const std::string& alloc_id,
                 const std::string& container_id, const std::string& agent_id,
                 int rank, const std::string& stdtype,
                 const std::string& line) {
  Json e = Json::object();
  e["task_id"] = task_id;
  e["allocation_id"] = alloc_id;
  e["container_id"] = container_id;
  e["agent_id"] = agent_id;
  e["rank_id"] = static_cast<int64_t>(rank);
  e["stdtype"] = stdtype;
  e["source"] = "task";
  e["level"] = stdtype == "stderr" ? "ERROR" : "INFO";
  e["log"] = line;
  det::MutexLock lock(g_log_mu);
  ++g_log_pending[task_id];
  g_log_queue.push_back(std::move(e));
  g_log_cv.notify_one();
}

// Called with g_log_mu held: account a batch's lines as delivered (or
// dropped) and wake drain waiters.
void settle_batch_locked(const std::vector<Json>& batch)
    REQUIRES(g_log_mu) {
  for (const auto& e : batch) {
    auto it = g_log_pending.find(e["task_id"].as_string());
    if (it != g_log_pending.end() && --it->second <= 0) {
      g_log_pending.erase(it);
    }
  }
}

void shipper_loop(const AgentOptions& opts) {
  while (g_running) {
    std::vector<Json> batch;
    {
      det::MutexLock lock(g_log_mu);
      g_log_cv.wait_for(lock.native(), std::chrono::milliseconds(500), [] {
        g_log_mu.AssertHeld();
        return !g_log_queue.empty() || !g_running;
      });
      while (!g_log_queue.empty() && batch.size() < 500) {
        batch.push_back(std::move(g_log_queue.front()));
        g_log_queue.pop_front();
      }
    }
    if (batch.empty()) continue;
    Json body = Json::object();
    Json logs = Json::array();
    for (const auto& e : batch) logs.push_back(e);
    body["logs"] = logs;
    bool delivered = false, poisoned = false;
    for (int attempt = 0; attempt < 3 && g_running; ++attempt) {
      try {
        auto r = master_call(opts.master_url, "POST",
                             "/api/v1/task/logs", body.dump(), 10.0);
        if (r.ok()) { delivered = true; break; }
        if (r.status >= 400 && r.status < 500) {
          // The master REJECTED the batch — retrying can't help and
          // would wedge every later line behind it.
          std::cerr << "agent: log batch rejected (" << r.status
                    << "), dropping " << batch.size() << " lines"
                    << std::endl;
          poisoned = true;
          break;
        }
      } catch (const std::exception&) {
      }
      std::this_thread::sleep_for(std::chrono::seconds(1));
    }
    if (delivered || poisoned) {
      det::MutexLock lock(g_log_mu);
      settle_batch_locked(batch);
      g_log_cv.notify_all();
      continue;
    }
    // Transient failure (master down/unreachable): the lines must NOT be
    // silently lost — completion implies logs durable now. Requeue at
    // the FRONT (order-preserving) and let the loop retry; the exit
    // report's own retry loop waits behind the same master.
    {
      det::MutexLock lock(g_log_mu);
      for (auto it = batch.rbegin(); it != batch.rend(); ++it) {
        g_log_queue.push_front(std::move(*it));
      }
    }
    std::this_thread::sleep_for(std::chrono::seconds(2));
  }
}

// Wait (bounded) until this task's tails drained their files and the
// shipper delivered everything they queued. Called before the exit
// report so a COMPLETED task's logs are already readable on the master
// (the reference drains its Collector before exiting,
// master/static/srv/ship_logs.py). Waits on THIS task's pending count
// only; skipped entirely when no tails were spawned (reattach paths that
// found the task already dead).
void drain_task_logs(std::shared_ptr<Task> task) {
  if (!task->tails_spawned) return;
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::seconds(15);
  while (task->tails_done.load() < 2 &&
         std::chrono::steady_clock::now() < deadline) {
    std::this_thread::sleep_for(std::chrono::milliseconds(50));
  }
  det::MutexLock lock(g_log_mu);
  g_log_cv.wait_until(lock.native(), deadline, [&task] {
    g_log_mu.AssertHeld();
    return g_log_pending.find(task->task_id) == g_log_pending.end() ||
           !g_running;
  });
}

// ---- persistent XLA compilation cache -----------------------------------
//
// Where the cache lives is decided from outside (docs/compile-farm.md,
// compile/runtime.py enable_compilation_cache): JAX_COMPILATION_CACHE_DIR
// in the agent's environment is inherited by every task untouched and is
// where pre-warmed entries go; only without it does the agent inject its
// own fixed host-local dir (work_root/xla_cache) as DET_XLA_CACHE_DIR.

bool cache_dir_placed_outside() {
  const char* p = getenv("JAX_COMPILATION_CACHE_DIR");
  return p != nullptr && *p != '\0';
}

std::string xla_cache_dir(const AgentOptions& opts) {
  return cache_dir_placed_outside()
             ? std::string(getenv("JAX_COMPILATION_CACHE_DIR"))
             : opts.work_root + "/xla_cache";
}

// Child-side, after fork. overwrite=0: an expconf environment_variables
// override (including the documented `DET_XLA_CACHE_DIR=` off switch) wins.
void inject_xla_cache_env(const AgentOptions& opts) {
  if (cache_dir_placed_outside()) return;
  setenv("DET_XLA_CACHE_DIR", xla_cache_dir(opts).c_str(), 0);
}

// ---- device detection ---------------------------------------------------

// Entries of `dir` named `prefix` followed by a number.
int count_dev_nodes(const char* dir, const char* prefix) {
  int count = 0;
  DIR* d = opendir(dir);
  if (d == nullptr) return 0;
  size_t plen = strlen(prefix);
  dirent* e;
  while ((e = readdir(d)) != nullptr) {
    if (strncmp(e->d_name, prefix, plen) != 0) continue;
    const char* rest = e->d_name + plen;
    if (*rest != '\0' && strspn(rest, "0123456789") == strlen(rest)) ++count;
  }
  closedir(d);
  return count;
}

int detect_tpu_chips() {
  // One /dev/accelN per chip with the accel driver; with vfio-pci (v5e
  // hosts) one IOMMU group node /dev/vfio/<N> per chip — /dev/vfio/vfio
  // is the container control node, not a chip.
  int n = count_dev_nodes("/dev", "accel");
  return n > 0 ? n : count_dev_nodes("/dev/vfio", "");
}

Json detect_slots(const AgentOptions& opts) {
  Json slots = Json::array();
  int n;
  std::string type;
  if (opts.slots_override >= 0) {
    n = opts.slots_override;
    type = opts.slot_type == "auto" ? "tpu" : opts.slot_type;
  } else if ((n = detect_tpu_chips()) > 0) {
    type = "tpu";
  } else if (opts.slot_type == "tpu") {
    // Asked for TPU slots and found no chip: registering a cpu slot
    // instead would schedule TPU trials onto a host that cannot run them.
    std::cerr << "agent: --slot-type tpu but no TPU chip found (no "
                 "/dev/accel* and no /dev/vfio/<group>); refusing to "
                 "register a cpu slot in its place" << std::endl;
    exit(2);
  } else {
    n = 1;  // no accelerator: one schedulable cpu slot per host
    type = "cpu";
  }
  for (int i = 0; i < n; ++i) {
    slots.push_back(Json(JsonObject{{"id", Json(static_cast<int64_t>(i))},
                                    {"type", Json(type)}}));
  }
  return slots;
}

// ---- task lifecycle -----------------------------------------------------
//
// Task stdout/stderr go to FILES in the task workdir (not pipes): files
// survive an agent restart, which is what makes reattach possible at all
// (reference container reattach, agent/internal/container/container.go:89
// — docker keeps the logs; here the filesystem does). A tail thread ships
// lines as they appear; the wrapper records the exit status to
// `.det_status` so even a non-child (adopted) task's exit code is
// recoverable.

void tail_thread(std::string path, std::shared_ptr<Task> task,
                 std::string agent_id, int rank, std::string stdtype,
                 std::atomic<long>* offset_slot) {
  FILE* f = nullptr;
  long offset = offset_slot->load();  // adoption resumes from the
                                      // persisted shipped offset
  std::string partial;
  char buf[8192];
  while (true) {
    // Sample exited BEFORE reading: if the flag flips between our fread
    // and the check we must loop for one more full read pass, or output
    // written in that window is lost (durability would silently break).
    bool exit_seen = task->exited.load();
    if (f == nullptr) {
      f = fopen(path.c_str(), "r");
      if (f != nullptr) fseek(f, offset, SEEK_SET);
    }
    size_t n = 0;
    if (f != nullptr) {
      n = fread(buf, 1, sizeof(buf), f);
      clearerr(f);  // EOF is transient while the task still runs
    }
    if (n > 0) {
      offset += static_cast<long>(n);
      offset_slot->store(offset);
      partial.append(buf, n);
      size_t nl;
      while ((nl = partial.find('\n')) != std::string::npos) {
        enqueue_log(task->task_id, task->allocation_id, task->container_id,
                    agent_id, rank, stdtype, partial.substr(0, nl));
        partial.erase(0, nl + 1);
      }
      continue;  // drain greedily
    }
    if (exit_seen) break;  // exited observed before this (empty) read
    std::this_thread::sleep_for(std::chrono::milliseconds(200));
  }
  if (!partial.empty()) {
    enqueue_log(task->task_id, task->allocation_id, task->container_id,
                agent_id, rank, stdtype, partial);
  }
  if (f != nullptr) fclose(f);
  task->tails_done.fetch_add(1);
}

// /proc/<pid>/stat field 22 (starttime, clock ticks since boot): the
// adoption identity — a recycled pid has a different starttime.
long long pid_starttime(pid_t pid) {
  std::ifstream f("/proc/" + std::to_string(pid) + "/stat");
  if (!f) return 0;
  std::string line;
  std::getline(f, line);
  // comm can contain spaces/parens: skip to the LAST ')'.
  auto close_paren = line.rfind(')');
  if (close_paren == std::string::npos) return 0;
  std::istringstream rest(line.substr(close_paren + 2));
  std::string tok;
  // fields 3..21 then starttime (field 22)
  for (int i = 0; i < 19; ++i) rest >> tok;
  long long start = 0;
  rest >> start;
  return start;
}

// ---- task registry: work_root/running.json -------------------------------
// Persisted on every start/exit so a restarted agent can reattach the
// tasks that survived it (reference containers/manager.go:76
// ReattachContainers).

det::Mutex g_registry_mu;  // one writer at a time for running.json
// (serializes a temp-file+rename sequence, not a data field — nothing
// is GUARDED_BY it)

void persist_registry(const AgentOptions& opts) {
  Json arr = Json::array();
  {
    det::MutexLock lock(g_mu);
    for (const auto& [cid, t] : g_tasks) {
      JsonObject e{
          {"container_id", Json(t->container_id)},
          {"allocation_id", Json(t->allocation_id)},
          {"task_id", Json(t->task_id)},
          {"workdir", Json(t->workdir)},
          {"pid", Json(static_cast<int64_t>(t->pid))},
          {"pid_start", Json(static_cast<int64_t>(t->pid_start))},
          {"rank", Json(static_cast<int64_t>(t->rank))},
          {"off_out", Json(static_cast<int64_t>(t->off_out.load()))},
          {"off_err", Json(static_cast<int64_t>(t->off_err.load()))},
      };
      // Exited-but-unreported tasks stay in the registry carrying their
      // exit code until the master confirms receipt.
      int pe = t->pending_exit.load();
      if (pe != INT_MIN) e["exit_code"] = Json(static_cast<int64_t>(pe));
      arr.push_back(Json(std::move(e)));
    }
  }
  // Serialize the write+rename: concurrent exiting tasks must not
  // interleave into a corrupt file.
  det::MutexLock lock(g_registry_mu);
  std::string path = opts.work_root + "/running.json";
  std::string tmp = path + ".tmp";
  std::ofstream f(tmp, std::ios::trunc);
  f << arr.dump();
  f.close();
  rename(tmp.c_str(), path.c_str());
}

// Flush shipped-log offsets every couple of seconds while tasks run —
// bounds reattach log duplication to the flush interval.
void registry_flusher(const AgentOptions& opts) {
  while (g_running) {
    std::this_thread::sleep_for(std::chrono::seconds(2));
    bool any;
    {
      det::MutexLock lock(g_mu);
      any = !g_tasks.empty();
    }
    if (any) persist_registry(opts);
  }
}

bool pid_alive(pid_t pid) {
  return pid > 0 && kill(pid, 0) == 0;
}

int read_status_file(const std::string& workdir, double wait_s) {
  // The sh wrapper writes the exit code to .det_status as its last act;
  // give it a moment to land after the process disappears.
  std::string path = workdir + "/.det_status";
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(static_cast<int>(wait_s * 1000));
  while (std::chrono::steady_clock::now() < deadline) {
    std::ifstream f(path);
    int code;
    if (f && (f >> code)) return code;
    std::this_thread::sleep_for(std::chrono::milliseconds(100));
  }
  return 137;  // unknowable → treat as killed
}

void report_state(const AgentOptions& opts, const std::string& alloc_id,
                  const Json& body) {
  std::string path = "/api/v1/agents/" + opts.id + "/allocations/" + alloc_id +
                     "/state";
  for (int attempt = 0; attempt < 5; ++attempt) {
    try {
      auto r = master_call(opts.master_url, "POST", path, body.dump(), 10.0);
      if (r.ok() || r.status == 404) return;
    } catch (const std::exception&) {
    }
    std::this_thread::sleep_for(std::chrono::seconds(1));
  }
}

// Fire-and-forget span delivery to the trial's lifecycle trace. Tracing
// is best-effort by contract: a dead master must never wedge task
// start/exit, so one attempt, failures logged and dropped.
void post_trial_spans(const AgentOptions& opts, long long trial_id,
                      const Json& spans) {
  if (trial_id <= 0 || spans.as_array().empty()) return;
  Json body = Json::object();
  body["spans"] = spans;
  try {
    auto r = master_call(opts.master_url, "POST",
                         "/api/v1/trials/" + std::to_string(trial_id) +
                             "/spans",
                         body.dump(), 5.0);
    if (!r.ok()) {
      std::cerr << "agent: span post rejected (" << r.status << ")"
                << std::endl;
    }
  } catch (const std::exception& e) {
    std::cerr << "agent: span post failed: " << e.what() << std::endl;
  }
}

void finish_task(const AgentOptions& opts, std::shared_ptr<Task> task,
                 int code) {
  task->exited = true;
  task->pending_exit = code;
  persist_registry(opts);  // the exit is durable BEFORE we try to report
  // Ship the remaining log lines BEFORE the exit report: the master flips
  // the task terminal on EXITED, and a user reading `det task logs` right
  // after must see the full output (bounded wait; a wedged master can't
  // hold the exit hostage forever).
  int64_t drain_t0 = det::trace::now_us();
  drain_task_logs(task);
  if (!task->trace_id.empty()) {
    Json spans = Json::array();
    spans.push_back(det::trace::make_span(
        task->trace_id, "agent.log_drain", drain_t0, det::trace::now_us(),
        "",
        Json(JsonObject{{"container_id", Json(task->container_id)},
                        {"exit_code", Json(static_cast<int64_t>(code))}})));
    post_trial_spans(opts, task->trial_id, spans);
  }
  Json done = Json::object();
  done["container_id"] = task->container_id;
  done["state"] = "EXITED";
  done["exit_code"] = static_cast<int64_t>(code);
  // Retry until the master confirms (2xx) or explicitly no longer knows
  // the allocation (404): an exit report lost to a master outage would
  // wedge the allocation in RUNNING forever. If the AGENT dies mid-retry,
  // the registry entry's exit_code lets the next incarnation resume this
  // loop.
  std::string path = "/api/v1/agents/" + opts.id + "/allocations/" +
                     task->allocation_id + "/state";
  while (g_running) {
    if (FAULT_POINT("agent.exit_report.drop") ==
        det::faults::Action::kDrop) {
      std::cerr << "agent: faultpoint dropped exit report for "
                << task->container_id << std::endl;
      std::this_thread::sleep_for(std::chrono::seconds(2));
      continue;
    }
    try {
      auto r = master_call(opts.master_url, "POST", path, done.dump(), 10.0);
      if (r.ok() || r.status == 404) break;
    } catch (const std::exception&) {
    }
    std::this_thread::sleep_for(std::chrono::seconds(2));
  }
  {
    det::MutexLock lock(g_mu);
    g_tasks.erase(task->container_id);
  }
  persist_registry(opts);
}

void supervise(const AgentOptions& opts, std::shared_ptr<Task> task) {
  // Start the log tails + the appropriate waiter.
  task->tails_spawned = true;
  std::thread(tail_thread, task->workdir + "/stdout.log", task, opts.id,
              task->rank, "stdout", &task->off_out).detach();
  std::thread(tail_thread, task->workdir + "/stderr.log", task, opts.id,
              task->rank, "stderr", &task->off_err).detach();
  if (!task->adopted) {
    std::thread([task, opts] {
      int status = 0;
      waitpid(task->pid, &status, 0);
      int code = WIFEXITED(status) ? WEXITSTATUS(status)
                                   : 128 + WTERMSIG(status);
      finish_task(opts, task, code);
    }).detach();
  } else {
    // Reattached task is NOT our child — waitpid is impossible. Poll
    // liveness; the wrapper's .det_status file carries the exit code.
    std::thread([task, opts] {
      while (pid_alive(task->pid)) {
        std::this_thread::sleep_for(std::chrono::milliseconds(500));
      }
      finish_task(opts, task, read_status_file(task->workdir, 3.0));
    }).detach();
  }
}

// ---- compile farm (docs/compile-farm.md) --------------------------------

// Minimal base64 decode (artifact blobs arrive b64 over the JSON API; the
// cache dirs need raw bytes).
std::string b64_decode(const std::string& in) {
  static bool init = false;
  static int8_t t[256];
  if (!init) {
    for (int i = 0; i < 256; ++i) t[i] = -1;
    const char* alpha =
        "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789+/";
    for (int i = 0; i < 64; ++i) t[static_cast<uint8_t>(alpha[i])] = i;
    init = true;
  }
  std::string out;
  out.reserve(in.size() * 3 / 4);
  int val = 0, bits = -8;
  for (unsigned char c : in) {
    if (t[c] < 0) {
      if (c == '=') break;
      continue;  // whitespace
    }
    val = (val << 6) | t[c];
    bits += 6;
    if (bits >= 0) {
      out.push_back(static_cast<char>((val >> bits) & 0xFF));
      bits -= 8;
    }
  }
  return out;
}

struct PrewarmResult {
  int files = 0;
  long long bytes = 0;
};

// Fetch the trial's precompiled artifacts BEFORE its container starts:
// aot-* executables land in work_root/aot_cache/<signature>/ (the harness
// deserializes them and skips trace+compile), everything else in the
// node's shared persistent XLA cache dir. Existing files are skipped —
// both stores are content-keyed, so a re-fetch is pure overlap time.
PrewarmResult prewarm_compile_cache(const AgentOptions& opts,
                                    const std::string& signature) {
  PrewarmResult res;
  HttpClientResponse r;
  try {
    r = master_call(opts.master_url, "GET",
                    "/api/v1/compile_cache/" + signature, "", 30.0);
  } catch (const std::exception& e) {
    std::cerr << "agent: compile-cache prewarm failed: " << e.what()
              << std::endl;
    return res;
  }
  if (!r.ok()) return res;
  Json doc = Json::parse_or_null(r.body);
  std::string aot_dir = opts.work_root + "/aot_cache";
  std::string sig_dir = aot_dir + "/" + signature;
  std::string xla_dir = xla_cache_dir(opts);
  mkdir(opts.work_root.c_str(), 0755);
  for (const auto& f : doc["files"].as_array()) {
    std::string name = f["name"].as_string("");
    // Artifact names are store keys, never paths.
    if (name.empty() || name.find('/') != std::string::npos ||
        name.find("..") != std::string::npos) {
      continue;
    }
    std::string dir = xla_dir;
    if (name.rfind("aot-", 0) == 0) {
      mkdir(aot_dir.c_str(), 0755);
      mkdir(sig_dir.c_str(), 0755);
      dir = sig_dir;
    } else {
      mkdir(xla_dir.c_str(), 0755);
    }
    std::string path = dir + "/" + name;
    struct stat st;
    if (stat(path.c_str(), &st) == 0) continue;  // already warm
    std::string raw = b64_decode(f["b64"].as_string(""));
    if (raw.empty()) continue;
    std::string tmp = path + ".tmp";
    std::ofstream out(tmp, std::ios::binary);
    out.write(raw.data(), static_cast<std::streamsize>(raw.size()));
    out.close();
    if (rename(tmp.c_str(), path.c_str()) == 0) {
      ++res.files;
      res.bytes += static_cast<long long>(raw.size());
    }
  }
  return res;
}

// Background AOT compile job dispatched by the master to this (idle)
// agent: run the harness compile worker; the worker reports DONE +
// artifacts itself, the agent only reports a crashed worker.
void run_compile_job(const AgentOptions& opts, const Json& action) {
  std::string sig = action["signature"].as_string("");
  const Json env = action["env"];
  std::string workdir =
      opts.work_root + "/compile-" + sig.substr(0, 12);
  mkdir(opts.work_root.c_str(), 0755);
  mkdir(workdir.c_str(), 0755);
  pid_t pid = fork();
  if (pid == 0) {
    setpgid(0, 0);
    int out_fd = open((workdir + "/worker.log").c_str(),
                      O_WRONLY | O_CREAT | O_APPEND, 0644);
    if (out_fd >= 0) {
      dup2(out_fd, STDOUT_FILENO);
      dup2(out_fd, STDERR_FILENO);
      close(out_fd);
    }
    if (chdir(workdir.c_str()) != 0) _exit(125);
    for (const auto& [k, v] : env.as_object()) {
      std::string val = v.is_string() ? v.as_string() : v.dump();
      setenv(k.c_str(), val.c_str(), 1);
    }
    // The worker compiles INTO the node's shared persistent cache, so
    // this host is warm before any artifact round-trips.
    inject_xla_cache_env(opts);
    execlp("python3", "python3", "-m", "determined_tpu.compile",
           static_cast<char*>(nullptr));
    _exit(127);
  }
  if (pid < 0) return;
  std::cerr << "agent: compile job " << sig.substr(0, 12) << " pid=" << pid
            << std::endl;
  std::thread([opts, sig, pid] {
    int status = 0;
    waitpid(pid, &status, 0);
    int code =
        WIFEXITED(status) ? WEXITSTATUS(status) : 128 + WTERMSIG(status);
    if (code != 0) {
      Json body = Json::object();
      body["state"] = "FAILED";
      body["error"] = "worker exited " + std::to_string(code);
      try {
        master_call(opts.master_url, "POST", "/api/v1/compile_jobs/" + sig,
                    body.dump(), 10.0);
      } catch (const std::exception&) {
      }
    }
    std::cerr << "agent: compile job " << sig.substr(0, 12) << " exited "
              << code << std::endl;
  }).detach();
}

void start_task(const AgentOptions& opts, const Json& action) {
  auto task = std::make_shared<Task>();
  task->allocation_id = action["allocation_id"].as_string();
  task->container_id = action["container_id"].as_string();
  const Json& env = action["env"];
  task->task_id = env["DET_TASK_ID"].as_string();
  task->rank = static_cast<int>(env["DET_NODE_RANK"].as_int(0));
  task->trial_id = env["DET_TRIAL_ID"].as_int(-1);
  task->trace_id = env["DET_TRACE_ID"].as_string();
  int64_t setup_t0 = det::trace::now_us();

  // Compile-farm cache warming (docs/compile-farm.md): fetch the trial's
  // precompiled artifacts CONCURRENTLY with workdir/log-file prep and join
  // before fork — the container starts with the node's XLA cache and the
  // signature's AOT executables already on disk, so the pre-warm cost is
  // overlap, not serial launch latency.
  std::string compile_sig = env["DET_COMPILE_SIGNATURE"].as_string("");
  PrewarmResult warm;
  int64_t warm_t0 = setup_t0, warm_t1 = setup_t0;
  std::thread warm_thread;
  if (!compile_sig.empty()) {
    warm_thread = std::thread([&opts, compile_sig, &warm, &warm_t1] {
      warm = prewarm_compile_cache(opts, compile_sig);
      warm_t1 = det::trace::now_us();
    });
  }

  std::string workdir = opts.work_root + "/" + task->allocation_id + "-r" +
                        std::to_string(task->rank);
  task->workdir = workdir;
  mkdir(opts.work_root.c_str(), 0755);
  mkdir(workdir.c_str(), 0755);

  // stdout/stderr to FILES (reattach survives us; the tail threads ship).
  int out_fd = open((workdir + "/stdout.log").c_str(),
                    O_WRONLY | O_CREAT | O_APPEND, 0644);
  int err_fd = open((workdir + "/stderr.log").c_str(),
                    O_WRONLY | O_CREAT | O_APPEND, 0644);
  if (out_fd < 0 || err_fd < 0) {
    if (out_fd >= 0) close(out_fd);
    if (err_fd >= 0) close(err_fd);
    std::cerr << "open log files failed in " << workdir << std::endl;
    // The master must not wait forever on an ASSIGNED container that
    // never launched.
    Json fail = Json::object();
    fail["container_id"] = task->container_id;
    fail["state"] = "EXITED";
    fail["exit_code"] = static_cast<int64_t>(125);
    report_state(opts, task->allocation_id, fail);
    if (warm_thread.joinable()) warm_thread.join();
    return;
  }

  // The cache must be fully warm before the trial process can race it.
  if (warm_thread.joinable()) warm_thread.join();

  pid_t pid = fork();
  if (pid == 0) {
    // Child: own process group so kill() reaps the whole task tree.
    setpgid(0, 0);
    dup2(out_fd, STDOUT_FILENO);
    dup2(err_fd, STDERR_FILENO);
    close(out_fd);
    close(err_fd);
    if (chdir(workdir.c_str()) != 0) _exit(125);
    // After chdir: a stale status in the task workdir must not mask this
    // run's exit (a SIGKILLed run writes none, and read_status_file would
    // otherwise return the previous run's code instead of 137).
    unlink(".det_status");
    for (const auto& [k, v] : env.as_object()) {
      std::string val = v.is_string() ? v.as_string() : v.dump();
      setenv(k.c_str(), val.c_str(), 1);
    }
    setenv("DET_WORKDIR", workdir.c_str(), 1);
    setenv("DET_RUN_DIR", workdir.c_str(), 1);
    setenv("PYTHONUNBUFFERED", "1", 1);
    if (!opts.master_cert_file.empty()) {
      // Trial processes verify the https master against the same pinned
      // CA the agent uses (reference: cert propagated into containers).
      setenv("DET_MASTER_CERT_FILE", opts.master_cert_file.c_str(), 1);
    }
    // Host-local persistent XLA compilation cache, shared across every
    // trial this agent runs: identical-shape ASHA rung trials skip the
    // retrace+compile that otherwise dominates short trials.
    inject_xla_cache_env(opts);
    // What the slots ARE and how many the host has: exec/launch.py binds
    // DET_SLOT_IDS to chips when they are tpu (libtpu otherwise claims
    // every chip of the host for the first task to start).
    setenv("DET_SLOT_TYPE", g_tpu_slots.load() ? "tpu" : "cpu", 1);
    setenv("DET_HOST_SLOTS", std::to_string(g_slots.load()).c_str(), 1);
    // Prewarmed AOT executables (compile farm); the harness looks in
    // $DET_COMPILE_AOT_DIR/$DET_COMPILE_SIGNATURE/.
    std::string aot_cache = opts.work_root + "/aot_cache";
    setenv("DET_COMPILE_AOT_DIR", aot_cache.c_str(), 0);
    // sh wrapper records the exit status to .det_status — that is what
    // lets a RESTARTED agent (which cannot waitpid an orphan) recover the
    // code. The in-container bootstrap (reference entrypoint.sh →
    // prep_container.py → launch.py) lives in the Python harness.
    execlp("/bin/sh", "sh", "-c",
           "python3 -m determined_tpu.exec.launch; st=$?; "
           "echo $st > .det_status; exit $st",
           static_cast<char*>(nullptr));
    _exit(127);
  }
  close(out_fd);
  close(err_fd);
  if (pid < 0) {
    std::cerr << "fork() failed" << std::endl;
    return;
  }
  int64_t fork_us = det::trace::now_us();
  task->pid = pid;
  task->pid_start = pid_starttime(pid);
  std::cerr << "agent: started " << task->container_id << " pid=" << pid
            << " workdir=" << workdir << std::endl;
  {
    det::MutexLock lock(g_mu);
    g_tasks[task->container_id] = task;
  }
  persist_registry(opts);
  supervise(opts, task);

  // Report RUNNING with our reachable address (feeds rendezvous).
  Json body = Json::object();
  body["container_id"] = task->container_id;
  body["state"] = "RUNNING";
  body["daemon_addr"] = opts.addr;
  report_state(opts, task->allocation_id, body);

  // Container-start phases on the trial's lifecycle trace: image_setup =
  // workdir + log-file prep (a real image pull on container runtimes),
  // container_start = fork to the RUNNING report landing.
  if (!task->trace_id.empty()) {
    Json attrs = Json(JsonObject{
        {"container_id", Json(task->container_id)},
        {"agent_id", Json(opts.id)},
        {"rank", Json(static_cast<int64_t>(task->rank))}});
    Json spans = Json::array();
    spans.push_back(det::trace::make_span(
        task->trace_id, "agent.image_setup", setup_t0, fork_us, "", attrs));
    if (!compile_sig.empty()) {
      Json wa = attrs;
      wa["signature"] = compile_sig;
      wa["files"] = static_cast<int64_t>(warm.files);
      wa["bytes"] = static_cast<int64_t>(warm.bytes);
      spans.push_back(det::trace::make_span(
          task->trace_id, "agent.cache_warm", warm_t0,
          warm_t1 > warm_t0 ? warm_t1 : det::trace::now_us(), "", wa));
    }
    spans.push_back(det::trace::make_span(
        task->trace_id, "agent.container_start", fork_us,
        det::trace::now_us(), "", attrs));
    post_trial_spans(opts, task->trial_id, spans);
  }
}

// Reattach tasks recorded by a previous agent incarnation (reference
// containers/manager.go:76 ReattachContainers): live pids are adopted
// (tail from EOF + /proc-poll waiter), dead ones get their exit reported
// from the wrapper's status file. Returns true if anything was adopted.
bool reattach_tasks(const AgentOptions& opts) {
  std::ifstream f(opts.work_root + "/running.json");
  if (!f) return false;
  std::stringstream ss;
  ss << f.rdbuf();
  Json arr = Json::parse_or_null(ss.str());
  bool adopted_any = false;
  for (const auto& e : arr.as_array()) {
    auto task = std::make_shared<Task>();
    task->container_id = e["container_id"].as_string();
    task->allocation_id = e["allocation_id"].as_string();
    task->task_id = e["task_id"].as_string();
    task->workdir = e["workdir"].as_string();
    task->pid = static_cast<pid_t>(e["pid"].as_int(-1));
    task->pid_start = e["pid_start"].as_int(0);
    task->rank = static_cast<int>(e["rank"].as_int(0));
    task->off_out = static_cast<long>(e["off_out"].as_int(0));
    task->off_err = static_cast<long>(e["off_err"].as_int(0));
    task->adopted = true;
    if (e["exit_code"].is_int()) {
      // Exited but the previous incarnation never got a confirmed
      // delivery: resume the report loop (off-thread; the master may
      // still be booting).
      int code = static_cast<int>(e["exit_code"].as_int());
      {
        det::MutexLock lock(g_mu);
        g_tasks[task->container_id] = task;
      }
      std::thread([task, opts, code] { finish_task(opts, task, code); })
          .detach();
      continue;
    }
    // Identity check: same pid AND same /proc starttime — a recycled pid
    // is some unrelated process, not our task.
    bool same_proc = pid_alive(task->pid) &&
                     pid_starttime(task->pid) == task->pid_start &&
                     task->pid_start != 0;
    if (same_proc) {
      std::cerr << "agent: reattached " << task->container_id << " pid="
                << task->pid << std::endl;
      {
        det::MutexLock lock(g_mu);
        g_tasks[task->container_id] = task;
      }
      supervise(opts, task);
      Json body = Json::object();
      body["container_id"] = task->container_id;
      body["state"] = "RUNNING";
      body["daemon_addr"] = opts.addr;
      report_state(opts, task->allocation_id, body);
      adopted_any = true;
    } else {
      std::cerr << "agent: task " << task->container_id
                << " died while we were down" << std::endl;
      int code = read_status_file(task->workdir, 0.5);
      {
        det::MutexLock lock(g_mu);
        g_tasks[task->container_id] = task;
      }
      // Ship whatever the dead task wrote after our previous incarnation's
      // last offset flush: exited is already set, so each tail does one
      // drain pass from the persisted offset to EOF and finishes; the
      // finish_task drain then waits for delivery before EXITED.
      task->exited = true;
      task->tails_spawned = true;
      std::thread(tail_thread, task->workdir + "/stdout.log", task,
                  opts.id, task->rank, "stdout", &task->off_out).detach();
      std::thread(tail_thread, task->workdir + "/stderr.log", task,
                  opts.id, task->rank, "stderr", &task->off_err).detach();
      std::thread([task, opts, code] { finish_task(opts, task, code); })
          .detach();
    }
  }
  persist_registry(opts);
  return adopted_any;
}

void kill_allocation(const std::string& alloc_id) {
  std::vector<std::shared_ptr<Task>> victims;
  {
    det::MutexLock lock(g_mu);
    for (auto& [cid, t] : g_tasks) {
      if (t->allocation_id == alloc_id) victims.push_back(t);
    }
  }
  for (auto& t : victims) {
    if (t->pid > 0 && !t->exited) {
      kill(-t->pid, SIGTERM);  // whole process group
    }
  }
  // Escalate after a grace period.
  std::thread([victims] {
    std::this_thread::sleep_for(std::chrono::seconds(15));
    for (auto& t : victims) {
      if (t->pid > 0 && !t->exited) kill(-t->pid, SIGKILL);
    }
  }).detach();
}

bool register_with_master(const AgentOptions& opts, bool reconnect) {
  Json body = Json::object();
  body["id"] = opts.id;
  body["resource_pool"] = opts.resource_pool;
  body["addr"] = opts.addr;
  body["reconnect"] = reconnect;
  body["preemptible"] = opts.preemptible;
  Json slots = detect_slots(opts);
  g_slots = static_cast<int>(slots.as_array().size());
  g_tpu_slots = g_slots > 0 &&
                slots.as_array()[0]["type"].as_string("") == "tpu";
  body["slots"] = slots;
  try {
    auto r = master_call(opts.master_url, "POST",
                         "/api/v1/agents/register", body.dump(), 10.0);
    if (!r.ok()) {
      // 401/403 means a credential problem, not a down master — say so,
      // or an unprovisioned agent spins forever with zero diagnostics.
      std::cerr << "agent: register failed (HTTP " << r.status << ")";
      if (r.status == 401 || r.status == 403) {
        std::cerr << " — agent token missing/invalid; set DET_AGENT_TOKEN "
                     "or --token-file to the master's <db>.agent_token";
      }
      std::cerr << std::endl;
      return false;
    }
    Json resp = Json::parse_or_null(r.body);
    if (!g_lease_ttl_pinned && resp["lease_ttl_s"].is_number()) {
      g_lease_ttl = resp["lease_ttl_s"].as_double();
    }
    renew_lease();  // a successful register is a lease renewal
    // Kill anything the master no longer recognizes (reattach reconcile).
    std::vector<std::string> keep;
    for (const auto& k : resp["keep_allocations"].as_array()) {
      keep.push_back(k.as_string());
    }
    std::vector<std::string> to_kill;
    {
      det::MutexLock lock(g_mu);
      for (auto& [cid, t] : g_tasks) {
        bool ok = false;
        for (const auto& k : keep) ok |= k == t->allocation_id;
        if (!ok) to_kill.push_back(t->allocation_id);
      }
    }
    for (const auto& aid : to_kill) kill_allocation(aid);
    return true;
  } catch (const std::exception& e) {
    std::cerr << "register failed: " << e.what() << std::endl;
    return false;
  }
}

// Reconnect after the master forgot us (404 = it restarted): re-register
// with capped exponential backoff + jitter so a herd of agents doesn't
// hammer a master that is still restoring, then re-report RUNNING for
// every live task — the restored master holds those allocations in state
// RESTORED and needs the claim to re-adopt them instead of declaring
// them lost at the reclaim deadline. One reconnect at a time: the
// heartbeat and action loops can both observe the 404.
std::atomic<bool> g_reconnecting{false};

void reconnect_master(const AgentOptions& opts) {
  if (g_reconnecting.exchange(true)) return;
  unsigned seed = static_cast<unsigned>(
      std::chrono::steady_clock::now().time_since_epoch().count());
  for (int attempt = 0; g_running; ++attempt) {
    if (register_with_master(opts, true)) break;
    agent_login(opts.master_url, /*use_env_token=*/true);
    // Equal jitter (backoff.h): full jitter could draw ~0 repeatedly and
    // still herd a restoring master.
    double delay = det::backoff::jittered_delay_s(attempt, &seed);
    std::this_thread::sleep_for(
        std::chrono::milliseconds(static_cast<int>(1000 * delay)));
  }
  std::vector<std::shared_ptr<Task>> live;
  {
    det::MutexLock lock(g_mu);
    for (auto& [cid, t] : g_tasks) {
      if (!t->exited) live.push_back(t);
    }
  }
  for (auto& t : live) {
    Json body = Json::object();
    body["container_id"] = t->container_id;
    body["state"] = "RUNNING";
    body["daemon_addr"] = opts.addr;
    report_state(opts, t->allocation_id, body);
  }
  g_reconnecting = false;
}

// Lease expiry = this side of a partition. Kill every local task NOW,
// before the master's reclaim deadline (agent_timeout_s > lease_ttl_s)
// reassigns their allocations to other nodes — otherwise two copies of
// the same trial run concurrently and the zombie's writes only die at the
// epoch fence (the backstop, not the plan). The agent itself stays up:
// when the partition heals it re-registers and is schedulable again.
void self_fence_tasks(const AgentOptions& opts) {
  std::vector<std::shared_ptr<Task>> live;
  {
    det::MutexLock lock(g_mu);
    for (auto& [cid, t] : g_tasks) {
      if (!t->exited) live.push_back(t);
    }
  }
  if (live.empty()) return;
  std::cerr << "agent: lease expired (" << g_lease_ttl.load()
            << "s without a heartbeat ack); self-fencing " << live.size()
            << " task(s) before the master reassigns" << std::endl;
  long long t0 = g_lease_renewed_wall_us.load();
  std::vector<std::string> allocs;
  for (auto& t : live) {
    if (!t->trace_id.empty()) {
      Json spans = Json::array();
      spans.push_back(det::trace::make_span(
          t->trace_id, "agent.lease", t0 > 0 ? t0 : det::trace::now_us(),
          det::trace::now_us(), "",
          Json(JsonObject{{"event", Json(std::string("self_fence"))},
                          {"lease_ttl_s", Json(g_lease_ttl.load())},
                          {"container_id", Json(t->container_id)}})));
      // Best-effort by nature: in a REAL partition this post is black-holed
      // too and the span is simply lost; in chaos runs (agent-side fault,
      // master reachable) it lands on the trial trace as evidence.
      post_trial_spans(opts, t->trial_id, spans);
    }
    bool seen = false;
    for (const auto& a : allocs) seen |= a == t->allocation_id;
    if (!seen) allocs.push_back(t->allocation_id);
  }
  for (const auto& aid : allocs) kill_allocation(aid);
}

void heartbeat_loop(const AgentOptions& opts) {
  while (g_running) {
    // Beat at TTL/3 (floor 0.5s, cap 10s) so a renewal can miss twice
    // before the lease lapses, and short test TTLs still get beats.
    double interval =
        std::min(10.0, std::max(0.5, g_lease_ttl.load() / 3.0));
    std::this_thread::sleep_for(
        std::chrono::milliseconds(static_cast<int>(1000 * interval)));
    // Expiry is judged BEFORE the partition faults below: a black-holed
    // agent must still notice its lease lapsed and self-fence.
    if (lease_remaining_s() <= 0 && !g_self_fenced.exchange(true)) {
      self_fence_tasks(opts);
    }
    if (FAULT_POINT("agent.heartbeat.blackhole") !=
        det::faults::Action::kNone) {
      // Sustained partition (docs/chaos.md): unlike the one-shot
      // agent.heartbeat.drop below, every heartbeat is swallowed while
      // armed. The action long-poll honors the same point, so the master
      // sees total silence and starts its reclaim clock.
      continue;
    }
    if (FAULT_POINT("agent.heartbeat.drop") == det::faults::Action::kDrop) {
      std::cerr << "agent: faultpoint dropped heartbeat" << std::endl;
      continue;
    }
    Json body = Json::object();
    Json running = Json::array();
    {
      det::MutexLock lock(g_mu);
      for (auto& [cid, t] : g_tasks) running.push_back(Json(t->allocation_id));
    }
    body["running"] = running;
    try {
      auto r = master_call(opts.master_url, "POST",
                           "/api/v1/agents/" + opts.id + "/heartbeat",
                           body.dump(), 10.0);
      if (r.status == 404) {
        reconnect_master(opts);  // master restarted
      } else if (r.ok()) {
        Json doc = Json::parse_or_null(r.body);
        if (!g_lease_ttl_pinned && doc["lease_ttl_s"].is_number()) {
          g_lease_ttl = doc["lease_ttl_s"].as_double();
        }
        renew_lease();  // the ack IS the lease renewal
        for (const auto& aid : doc["kill_allocations"].as_array()) {
          kill_allocation(aid.as_string());
        }
      }
    } catch (const std::exception&) {
      // master temporarily unreachable; keep running tasks (reference
      // reconnect-with-reattach, agent.go:330-362). The lease clock keeps
      // ticking — sustained unreachability ends in self_fence_tasks above.
    }
  }
}

// ---- node-local /metrics ------------------------------------------------
//
// Prometheus text exposition for THIS node (docs/observability.md): the
// master's /metrics sees the fleet through its own state machine; the
// agent endpoint is the ground truth a per-node scrape needs — what is
// actually running here, how far behind the log shipper is, and whether
// a termination notice has this node draining. Unauthenticated by
// design: it binds for node-local/VPC scrapers and carries no secrets,
// the same posture as a node_exporter.

det::HttpResponse agent_metrics_response() {
  int running = 0, exited_pending = 0;
  {
    det::MutexLock lock(g_mu);
    for (const auto& [cid, t] : g_tasks) {
      if (t->exited) {
        ++exited_pending;
      } else {
        ++running;
      }
    }
  }
  long backlog = 0;
  {
    det::MutexLock lock(g_log_mu);
    for (const auto& [tid, n] : g_log_pending) backlog += n;
  }
  double uptime = std::chrono::duration<double>(
                      std::chrono::steady_clock::now() - g_started)
                      .count();
  std::ostringstream out;
  out << "# TYPE det_agent_slots gauge\n"
      << "det_agent_slots " << g_slots.load() << "\n"
      << "# TYPE det_agent_tasks gauge\n"
      << "det_agent_tasks{state=\"running\"} " << running << "\n"
      << "det_agent_tasks{state=\"exited_pending_report\"} "
      << exited_pending << "\n"
      << "# TYPE det_agent_log_backlog_lines gauge\n"
      << "det_agent_log_backlog_lines " << backlog << "\n"
      << "# TYPE det_agent_draining gauge\n"
      << "det_agent_draining " << (g_draining.load() ? 1 : 0) << "\n"
      << "# TYPE det_agent_lease_remaining_seconds gauge\n"
      << "det_agent_lease_remaining_seconds "
      << std::max(0.0, lease_remaining_s()) << "\n"
      << "# TYPE det_agent_uptime_seconds gauge\n"
      << "det_agent_uptime_seconds " << uptime << "\n";
  det::HttpResponse r;
  r.status = 200;
  r.content_type = "text/plain; version=0.0.4";
  r.body = out.str();
  return r;
}

// ---- termination-notice watcher -----------------------------------------
//
// Infrastructure gives seconds, not minutes: a GCE spot preemption or TPU
// maintenance event (and a SIGTERM aimed at this daemon) means the whole
// node disappears at a hard deadline. The watcher detects the notice from
// one of the pluggable sources, POSTs it to the master — which marks the
// agent DRAINING and pushes a deadline-extended preemption to every trial
// on it — and then deliberately does NOT tear anything down: tasks get
// the grace window to emergency-checkpoint and exit, and the log
// shipper/exit reporters keep draining until the node actually dies.

void post_preempt_notice(const AgentOptions& opts, double deadline_s,
                         const std::string& reason) {
  Json body = Json::object();
  body["deadline_seconds"] = deadline_s;
  body["reason"] = reason;
  std::string path = "/api/v1/agents/" + opts.id + "/preempt_notice";
  for (int attempt = 0; attempt < 5 && g_running; ++attempt) {
    try {
      auto r = master_call(opts.master_url, "POST", path, body.dump(), 5.0);
      if (r.ok() || r.status == 404) return;
    } catch (const std::exception&) {
    }
    std::this_thread::sleep_for(std::chrono::milliseconds(500));
  }
  std::cerr << "agent: preempt notice undeliverable; master will fall back "
               "to the heartbeat-timeout path" << std::endl;
}

// GCE metadata termination sources (reference: provisioner spot handling;
// cloud.google.com/compute/docs/instances/preemptible#preemption):
// instance/preempted flips to TRUE, maintenance-event to TERMINATE_*.
// Returns the notice reason, or "" when no event is pending.
std::string poll_gce_notice(const AgentOptions& opts) {
  const std::map<std::string, std::string> hdrs = {
      {"Metadata-Flavor", "Google"}};
  try {
    auto r = det::http_request(
        "GET", opts.gce_metadata_url,
        "/computeMetadata/v1/instance/preempted", "", 2.0, hdrs);
    if (r.ok() && r.body.find("TRUE") != std::string::npos) {
      return "spot_preemption";
    }
    r = det::http_request(
        "GET", opts.gce_metadata_url,
        "/computeMetadata/v1/instance/maintenance-event", "", 2.0, hdrs);
    if (r.ok() && r.body.find("TERMINATE") != std::string::npos) {
      return "host_maintenance";
    }
  } catch (const std::exception&) {
    // not on GCE / metadata server unreachable: silently no notice
  }
  return "";
}

// Runtime fault seam (docs/chaos.md): the master arms its points mid-run
// through POST /api/v1/debug/faults, but the agent has no admin API — so
// chaos tests arm AGENT points mid-run through a watched file
// (DET_AGENT_FAULTS_FILE), the same pattern as notice_file. When the file
// appears (or its spec changes) the registry is reset and re-armed from
// its content; when it disappears all points disarm — "healing" a
// partition armed as agent.heartbeat.blackhole.
void faults_file_watch_loop(const std::string& path) {
  std::string current;
  bool ever_seen = false;
  while (g_running) {
    std::this_thread::sleep_for(std::chrono::milliseconds(300));
    std::string spec;
    {
      std::ifstream f(path);
      if (f) {
        std::stringstream ss;
        ss << f.rdbuf();
        spec = ss.str();
      }
    }
    while (!spec.empty() &&
           (spec.back() == '\n' || spec.back() == '\r' ||
            spec.back() == ' ' || spec.back() == '\t')) {
      spec.pop_back();
    }
    if (spec == current) continue;
    if (spec.empty() && !ever_seen) continue;  // no file yet, nothing armed
    det::faults::disarm_all();
    current = spec;
    if (spec.empty()) {
      std::cerr << "agent: faults file removed; all points disarmed"
                << std::endl;
      continue;
    }
    ever_seen = true;
    std::string err;
    if (det::faults::arm_from_spec(spec, &err)) {
      std::cerr << "agent: armed faults from file: " << spec << std::endl;
    } else {
      std::cerr << "agent: bad faults file spec '" << spec << "': " << err
                << std::endl;
    }
  }
}

void notice_watch_loop(const AgentOptions& opts) {
  double default_deadline = 30.0;
  if (const char* p = getenv("DET_AGENT_PREEMPT_DEADLINE_S")) {
    default_deadline = atof(p);
  }
  bool notified = false;
  auto shutdown_at = std::chrono::steady_clock::time_point::max();
  auto last_gce = std::chrono::steady_clock::now() - std::chrono::hours(1);
  while (g_running) {
    std::this_thread::sleep_for(std::chrono::milliseconds(500));
    if (std::chrono::steady_clock::now() >= shutdown_at) {
      // SIGTERM grace window over: stop the loops and let main() return.
      std::cerr << "agent: grace window closed; exiting" << std::endl;
      g_running = false;
      g_log_cv.notify_all();
      break;
    }
    if (notified) {
      // A SIGTERM'd agent whose tasks have all exited and whose log
      // queue is drained has nothing left to protect — exit now instead
      // of idling out the rest of the grace window (keeps
      // `det deploy local down` snappy).
      if (g_sigterm.load() && !has_running_tasks()) {
        bool drained;
        {
          det::MutexLock lock(g_log_mu);
          drained = g_log_queue.empty() && g_log_pending.empty();
        }
        if (drained) {
          std::cerr << "agent: SIGTERM drain complete; exiting" << std::endl;
          g_running = false;
          g_log_cv.notify_all();
          break;
        }
      }
      continue;
    }
    double deadline = -1;
    std::string reason;
    if (g_sigterm.load()) {
      deadline = opts.term_grace_s;
      reason = "agent_sigterm";
    } else if (has_running_tasks() &&
               FAULT_POINT("agent.preempt.notice") !=
                   det::faults::Action::kNone) {
      // Chaos (docs/chaos.md): deterministic spot kill. Gated on a
      // running task so an env-armed point fires MID-TRIAL, which is the
      // scenario worth testing, not at agent boot.
      deadline = default_deadline;
      reason = "spot_preemption";
    } else if (!opts.notice_file.empty()) {
      std::ifstream f(opts.notice_file);
      if (f) {
        std::stringstream ss;
        ss << f.rdbuf();
        Json j = Json::parse_or_null(ss.str());
        deadline = j["deadline_seconds"].as_double(default_deadline);
        reason = j["reason"].as_string("spot_preemption");
      }
    } else if (opts.notice_source == "gce" &&
               std::chrono::steady_clock::now() - last_gce >
                   std::chrono::seconds(5)) {
      last_gce = std::chrono::steady_clock::now();
      reason = poll_gce_notice(opts);
      if (!reason.empty()) deadline = default_deadline;
    }
    if (deadline >= 0 && !reason.empty()) {
      notified = true;
      g_draining = true;  // surfaced on /metrics (det_agent_draining)
      std::cerr << "agent: termination notice (" << reason << "), deadline "
                << deadline << "s" << std::endl;
      post_preempt_notice(opts, deadline, reason);
      if (reason == "agent_sigterm") {
        // The notice sources other than SIGTERM mean the NODE dies on its
        // own; for SIGTERM we own the exit — after deadline + drain slack.
        shutdown_at = std::chrono::steady_clock::now() +
                      std::chrono::milliseconds(
                          static_cast<int64_t>((deadline + 10.0) * 1000));
      }
    }
  }
}

}  // namespace

int main(int argc, char** argv) {
  AgentOptions opts;
  char hostname[256] = "agent";
  gethostname(hostname, sizeof(hostname));
  opts.id = hostname;
  opts.addr = "127.0.0.1";

  // Config precedence flags > env > JSON config file — the same
  // viper-style layering as the master (reference
  // agent/internal/options/options.go reads agent.yaml the same way).
  std::string cfg_path;
  if (const char* p = getenv("DET_AGENT_CONFIG")) cfg_path = p;
  for (int i = 1; i < argc - 1; ++i) {
    if (strcmp(argv[i], "--config") == 0) cfg_path = argv[i + 1];
  }
  if (!cfg_path.empty()) {
    std::ifstream f(cfg_path);
    if (!f) {
      std::cerr << "cannot read config " << cfg_path << std::endl;
      return 1;
    }
    std::stringstream ss;
    ss << f.rdbuf();
    Json j = Json::parse_or_null(ss.str());
    if (!j.is_object()) {
      std::cerr << "config " << cfg_path << " is not a JSON object"
                << std::endl;
      return 1;
    }
    if (j["master_url"].is_string()) opts.master_url = j["master_url"].as_string();
    if (j["id"].is_string()) opts.id = j["id"].as_string();
    if (j["resource_pool"].is_string()) {
      opts.resource_pool = j["resource_pool"].as_string();
    }
    if (j["addr"].is_string()) opts.addr = j["addr"].as_string();
    if (j["work_root"].is_string()) opts.work_root = j["work_root"].as_string();
    if (j["token_file"].is_string()) opts.token_file = j["token_file"].as_string();
    if (j["master_cert_file"].is_string()) {
      opts.master_cert_file = j["master_cert_file"].as_string();
    }
    if (j["slots"].is_number()) {
      opts.slots_override = static_cast<int>(j["slots"].as_int());
    }
    if (j["slot_type"].is_string()) opts.slot_type = j["slot_type"].as_string();
    if (j["preemptible"].is_bool()) {
      opts.preemptible = j["preemptible"].as_bool();
    }
    if (j["term_grace_s"].is_number()) {
      opts.term_grace_s = j["term_grace_s"].as_double();
    }
    if (j["notice_source"].is_string()) {
      opts.notice_source = j["notice_source"].as_string();
    }
    if (j["notice_file"].is_string()) {
      opts.notice_file = j["notice_file"].as_string();
    }
    if (j["metrics_port"].is_number()) {
      opts.metrics_port = static_cast<int>(j["metrics_port"].as_int());
    }
    if (j["lease_ttl_s"].is_number()) {
      opts.lease_ttl_s = j["lease_ttl_s"].as_double();
    }
  }

  if (const char* p = getenv("DET_MASTER")) opts.master_url = p;
  if (const char* p = getenv("DET_AGENT_SLOTS")) {
    opts.slots_override = atoi(p);
  }
  if (const char* p = getenv("DET_AGENT_TOKEN_FILE")) opts.token_file = p;
  if (const char* p = getenv("DET_AGENT_PREEMPTIBLE")) {
    opts.preemptible = std::string(p) == "1" || std::string(p) == "true";
  }
  if (const char* p = getenv("DET_MASTER_CERT_FILE")) {
    opts.master_cert_file = p;
  }
  if (const char* p = getenv("DET_AGENT_TERM_GRACE_S")) {
    opts.term_grace_s = atof(p);
  }
  if (const char* p = getenv("DET_AGENT_NOTICE_SOURCE")) {
    opts.notice_source = p;
  }
  if (const char* p = getenv("DET_AGENT_NOTICE_FILE")) opts.notice_file = p;
  if (const char* p = getenv("DET_AGENT_METRICS_PORT")) {
    opts.metrics_port = atoi(p);
  }
  if (const char* p = getenv("DET_AGENT_GCE_METADATA_URL")) {
    opts.gce_metadata_url = p;
  }
  if (const char* p = getenv("DET_AGENT_LEASE_TTL_S")) {
    opts.lease_ttl_s = atof(p);
  }

  for (int i = 1; i < argc; ++i) {
    std::string a = argv[i];
    auto next = [&]() -> std::string {
      return i + 1 < argc ? argv[++i] : "";
    };
    if (a == "--master-url") opts.master_url = next();
    else if (a == "--id") opts.id = next();
    else if (a == "--resource-pool") opts.resource_pool = next();
    else if (a == "--addr") opts.addr = next();
    else if (a == "--slots") opts.slots_override = atoi(next().c_str());
    else if (a == "--slot-type") opts.slot_type = next();
    else if (a == "--preemptible") opts.preemptible = true;
    else if (a == "--work-root") opts.work_root = next();
    else if (a == "--token-file") opts.token_file = next();
    else if (a == "--master-cert-file") opts.master_cert_file = next();
    else if (a == "--term-grace") opts.term_grace_s = atof(next().c_str());
    else if (a == "--notice-source") opts.notice_source = next();
    else if (a == "--notice-file") opts.notice_file = next();
    else if (a == "--metrics-port") opts.metrics_port = atoi(next().c_str());
    else if (a == "--lease-ttl") opts.lease_ttl_s = atof(next().c_str());
    else if (a == "--config") next();
    else if (a == "--help" || a == "-h") {
      std::cout << "determined-agent [--config agent.json] --master-url URL "
                   "[--id ID] [--resource-pool P] [--addr A] [--slots N] "
                   "[--slot-type tpu|cpu] [--preemptible] [--work-root DIR] "
                   "[--token-file PATH] [--term-grace SECONDS] "
                   "[--notice-source gce] [--notice-file PATH] "
                   "[--metrics-port N  (0 off, -1 ephemeral)] "
                   "[--lease-ttl SECONDS]\n";
      return 0;
    }
  }
  g_token_file = opts.token_file;
  if (!opts.master_cert_file.empty()) {
    det::set_https_ca_file(opts.master_cert_file);
  }

  signal(SIGPIPE, SIG_IGN);
  // SIGTERM = termination notice, handled by the notice watcher — the
  // default (immediate death) would drop the grace window spot capacity
  // explicitly grants.
  signal(SIGTERM, handle_sigterm);
  det::faults::arm_from_env();  // DET_FAULTS chaos points (docs/chaos.md)
  if (const char* p = getenv("DET_AGENT_FAULTS_FILE")) {
    std::thread(faults_file_watch_loop, std::string(p)).detach();
  }
  if (opts.lease_ttl_s > 0) {
    g_lease_ttl = opts.lease_ttl_s;
    g_lease_ttl_pinned = true;
  }

  // Install the bootstrap credential (env first, then token file), adopt
  // any tasks that survived a previous agent incarnation, then register
  // (retry until master is up — the file may not exist until the master
  // has booted and minted it). reconnect=true when anything was adopted
  // so the master runs the reattach reconcile instead of a fresh reset.
  agent_login(opts.master_url, /*use_env_token=*/true);
  mkdir(opts.work_root.c_str(), 0755);
  bool adopted = reattach_tasks(opts);
  // Jittered retry (backoff.h): a whole fleet booting against a master
  // that isn't up yet must not re-register in lockstep once it is.
  unsigned boot_seed = static_cast<unsigned>(getpid()) ^
                       static_cast<unsigned>(
                           std::chrono::steady_clock::now()
                               .time_since_epoch()
                               .count());
  for (int attempt = 0; !register_with_master(opts, adopted); ++attempt) {
    agent_login(opts.master_url, /*use_env_token=*/true);
    double delay =
        det::backoff::jittered_delay_s(attempt, &boot_seed, 1.0, 10.0);
    std::this_thread::sleep_for(
        std::chrono::milliseconds(static_cast<int>(1000 * delay)));
  }
  std::cout << "agent " << opts.id << " registered with " << opts.master_url
            << std::endl;

  // Node-local Prometheus endpoint (docs/observability.md). Started after
  // registration so det_agent_slots reflects what the master was told.
  det::HttpServer metrics_server;
  if (opts.metrics_port != 0) {
    try {
      int port = metrics_server.listen(
          "0.0.0.0", opts.metrics_port < 0 ? 0 : opts.metrics_port,
          [](const det::HttpRequest& req) {
            if (req.path == "/metrics" && req.method == "GET") {
              return agent_metrics_response();
            }
            if (req.path == "/healthz") {
              return det::HttpResponse::json(200, "{\"status\":\"ok\"}");
            }
            return det::HttpResponse::json(404,
                                           "{\"error\":\"not found\"}");
          });
      metrics_server.start();
      // Parseable by the devcluster harness when an ephemeral port was
      // requested.
      std::cout << "agent metrics on port " << port << std::endl;
    } catch (const std::exception& e) {
      std::cerr << "agent: metrics endpoint failed to bind ("
                << e.what() << "); continuing without it" << std::endl;
    }
  }

  std::thread(shipper_loop, std::cref(opts)).detach();
  std::thread(heartbeat_loop, std::cref(opts)).detach();
  std::thread(registry_flusher, std::cref(opts)).detach();
  std::thread(notice_watch_loop, std::cref(opts)).detach();

  // Action long-poll loop.
  std::string actions_path = "/api/v1/agents/" + opts.id +
                             "/actions?timeout_seconds=" +
                             std::to_string(opts.poll_timeout_s);
  while (g_running) {
    if (FAULT_POINT("agent.heartbeat.blackhole") !=
        det::faults::Action::kNone) {
      // A partition silences EVERY master-bound channel, and the long-poll
      // also refreshes master-side last_heartbeat — if it kept running the
      // master would never start its reclaim clock and the blackhole would
      // simulate nothing.
      std::this_thread::sleep_for(std::chrono::milliseconds(500));
      continue;
    }
    try {
      auto r = master_call(opts.master_url, "GET", actions_path, "",
                           opts.poll_timeout_s + 10.0);
      if (r.status == 404) {
        reconnect_master(opts);
        continue;
      }
      if (!r.ok()) {
        std::this_thread::sleep_for(std::chrono::seconds(1));
        continue;
      }
      // Bind the parsed document to a named value: iterating a reference
      // obtained through a temporary would dangle.
      Json doc = Json::parse_or_null(r.body);
      for (const auto& action : doc["actions"].as_array()) {
        const std::string& type = action["type"].as_string();
        std::cerr << "agent: action " << type << " alloc="
                  << action["allocation_id"].as_string() << std::endl;
        if (type == "start") {
          start_task(opts, action);
        } else if (type == "compile") {
          run_compile_job(opts, action);
        } else if (type == "kill") {
          kill_allocation(action["allocation_id"].as_string());
        }
      }
    } catch (const std::exception&) {
      std::this_thread::sleep_for(std::chrono::seconds(2));
    }
  }
  return 0;
}
