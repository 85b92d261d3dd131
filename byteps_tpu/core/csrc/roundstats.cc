#include "roundstats.h"

#include <algorithm>
#include <cstdlib>
#include <cstring>

#include "metrics.h"
#include "tenancy.h"
#include "trace.h"

namespace bps {

namespace {

int64_t EnvLL(const char* name, int64_t dflt) {
  const char* v = getenv(name);
  return v && *v ? atoll(v) : dflt;
}

bool EnvOn(const char* name, bool dflt) {
  const char* v = getenv(name);
  if (!v || !*v) return dflt;
  return strcmp(v, "0") != 0 && strcasecmp(v, "false") != 0 &&
         strcasecmp(v, "off") != 0 && strcasecmp(v, "no") != 0;
}

// Rounds legally overlap: double buffering keeps r and r+1 live, and a
// deep-pipelining caller keeps up to ~4 in flight. An open round this
// far behind the newest with its ENQ/DONE ledger still unbalanced is
// wedged or abandoned (a failed handle) — force-finalize so the table
// stays bounded and the ring keeps moving.
constexpr int kOpenRounds = 8;

// An open round's interval list is merged in place when it gets this
// long, so a round of any size keeps a bounded list per stage.
constexpr size_t kCompactAt = 8192;

// Sort and merge in place; returns the union's length.
int64_t MergeIntervals(std::vector<RoundInterval>* v) {
  std::sort(v->begin(), v->end(),
            [](const RoundInterval& a, const RoundInterval& b) {
              return a.start < b.start;
            });
  size_t n = 0;
  int64_t total = 0;
  for (const RoundInterval& i : *v) {
    if (n && i.start <= (*v)[n - 1].end) {
      if (i.end > (*v)[n - 1].end) {
        total += i.end - (*v)[n - 1].end;
        (*v)[n - 1].end = i.end;
      }
    } else {
      total += i.end - i.start;
      (*v)[n++] = i;
    }
  }
  v->resize(n);
  return total;
}

// `span` (local rounds only): the elapsed-time fields follow `wall_us`.
// Fleet records came over the heartbeat wire and have none.
void AppendRec(std::string* out, const RoundRec& r,
               const RoundSpan* span = nullptr,
               const RoundBusy* busy = nullptr) {
  char buf[2048];
  int n = snprintf(buf, sizeof(buf),
           "{\"round\":%d,\"parts\":%d,\"queue_us\":%lld,"
           "\"comp_us\":%lld,\"push_us\":%lld,\"sum_us\":%lld,"
           "\"wire_ack_us\":%lld,\"pull_us\":%lld,\"dec_us\":%lld,"
           "\"wire_bytes\":%lld,\"wire_msgs\":%d,\"fused_frames\":%d,"
           "\"retries\":%d,\"parked\":%d,\"wall_us\":%lld",
           r.round, r.parts, static_cast<long long>(r.queue_us),
           static_cast<long long>(r.comp_us),
           static_cast<long long>(r.push_us),
           static_cast<long long>(r.sum_us),
           static_cast<long long>(
               r.push_us > r.sum_us ? r.push_us - r.sum_us : 0),
           static_cast<long long>(r.pull_us),
           static_cast<long long>(r.dec_us),
           static_cast<long long>(r.wire_bytes), r.wire_msgs,
           r.fused_frames, r.retries, r.parked,
           static_cast<long long>(RoundWallUs(r)));
  if (span) {
    // Offsets are from start_us (the first enqueue); a stage that never
    // ran reads 0 / 0.
    auto rel = [&](int64_t t) {
      return static_cast<long long>(t ? t - span->first_enq_us : 0);
    };
    n += snprintf(
        buf + n, sizeof(buf) - n,
        ",\"start_us\":%lld,\"elapsed_us\":%lld,"
        "\"push_offset_us\":%lld,\"push_window_us\":%lld,"
        "\"pull_offset_us\":%lld,\"pull_window_us\":%lld",
        static_cast<long long>(span->first_enq_us),
        static_cast<long long>(span->first_enq_us && span->last_done_us
                                   ? span->last_done_us - span->first_enq_us
                                   : 0),
        rel(span->push_start_us),
        static_cast<long long>(span->push_end_us - span->push_start_us),
        rel(span->pull_start_us),
        static_cast<long long>(span->pull_end_us - span->pull_start_us));
  }
  if (busy) {
    auto ll = [](int64_t v) { return static_cast<long long>(v); };
    n += snprintf(
        buf + n, sizeof(buf) - n,
        ",\"queue_span_us\":%lld,\"comp_span_us\":%lld,"
        "\"push_span_us\":%lld,\"sum_span_us\":%lld,"
        "\"pull_span_us\":%lld,\"dec_span_us\":%lld,"
        "\"feed_wait_us\":%lld,\"credit_blocked_us\":%lld,"
        "\"push_thread_us\":%lld,\"push_thread_sum_us\":%lld,"
        "\"send_blocked_us\":%lld,\"send_blocked_sum_us\":%lld,"
        "\"server_us\":%lld,\"server_span_us\":%lld,"
        "\"recv_thread_us\":%lld,\"recv_thread_sum_us\":%lld,"
        "\"van_recv_us\":%lld",
        ll(busy->span_us[RS_QUEUE]), ll(busy->span_us[RS_COMP]),
        ll(busy->span_us[RS_PUSH]), ll(busy->span_us[RS_SUM]),
        ll(busy->span_us[RS_PULL]), ll(busy->span_us[RS_DEC]),
        ll(busy->feed_wait_us), ll(busy->span_us[RS_CREDIT]),
        ll(busy->span_us[RS_PUSHTHR]), ll(busy->sum_us[RS_PUSHTHR]),
        ll(busy->span_us[RS_SENDBLK]), ll(busy->sum_us[RS_SENDBLK]),
        ll(busy->sum_us[RS_SERVER]), ll(busy->span_us[RS_SERVER]),
        ll(busy->span_us[RS_RECVTHR]), ll(busy->sum_us[RS_RECVTHR]),
        ll(busy->span_us[RS_VANRECV]));
  }
  *out += buf;
  *out += "}";
}

}  // namespace

RoundStats::RoundStats()
    : ring_cap_(static_cast<size_t>(EnvLL("BYTEPS_ROUNDSTATS_RING", 256))) {
  if (ring_cap_ < 8) ring_cap_ = 8;
  ring_.resize(ring_cap_);
  spans_.resize(ring_cap_);
  busy_.resize(ring_cap_);
  armed_.store(EnvOn("BYTEPS_ROUNDSTATS_ON", true),
               std::memory_order_relaxed);
  heartbeat_summary_on_ = EnvOn("BYTEPS_ROUNDSTATS_HEARTBEAT_SUMMARY", true);
}

RoundStats& RoundStats::Get() {
  static RoundStats* inst = new RoundStats();
  return *inst;
}

void RoundStats::SetNode(int role, int node_id) {
  role_.store(role, std::memory_order_relaxed);
  node_id_.store(node_id, std::memory_order_relaxed);
}

void RoundStats::SetNodeTenant(int node_id, int tenant) {
  std::lock_guard<std::mutex> lk(mu_);
  node_tenant_[node_id] = tenant;
}

void RoundStats::Track(int32_t stage, int round, int64_t us,
                       int64_t bytes, int64_t now_us) {
  if (!On() || round < 0 || stage < 0 || stage >= RS_STAGES) return;
  const int64_t now = now_us ? now_us : NowUs();
  std::lock_guard<std::mutex> lk(mu_);
  auto keep = [stage, us, now](OpenRound* o) {
    std::vector<RoundInterval>& iv = o->iv[stage];
    iv.push_back({now - us, now});
    if (iv.size() >= kCompactAt) MergeIntervals(&iv);
  };
  if (stage > RS_DONE) {
    // A resource's stamp describes a round, it never opens one: the last
    // callback of a round ends after its RS_DONE, and by then the round
    // may have been finalized.
    auto it = open_.find(round);
    if (it == open_.end() || us <= 0) return;
    it->second.busy.sum_us[stage] += us;
    keep(&it->second);
    return;
  }
  OpenRound& o = open_[round];
  o.rec.round = round;
  // Four of the stages also stamp the round's elapsed time (RoundSpan).
  // A window opens at the earliest issue (now - us) and closes at the
  // latest completion.
  auto widen = [us, now](int64_t* start, int64_t* end) {
    if (*start == 0 || now - us < *start) *start = now - us;
    if (now > *end) *end = now;
  };
  switch (stage) {
    case RS_ENQ:
      if (o.enqueued == o.done) o.open_since_us = now;
      ++o.enqueued;
      if (o.span.first_enq_us == 0) o.span.first_enq_us = now;
      break;
    case RS_QUEUE: o.rec.queue_us += us; break;
    case RS_COMP:  o.rec.comp_us += us; break;
    case RS_PUSH:
      o.rec.push_us += us;
      o.rec.wire_bytes += bytes;
      widen(&o.span.push_start_us, &o.span.push_end_us);
      break;
    case RS_SUM:   o.rec.sum_us += us; break;
    case RS_PULL:
      o.rec.pull_us += us;
      o.rec.wire_bytes += bytes;
      widen(&o.span.pull_start_us, &o.span.pull_end_us);
      break;
    case RS_DEC:   o.rec.dec_us += us; break;
    case RS_RETRY: ++o.rec.retries; break;
    case RS_PARK:  ++o.rec.parked; break;
    case RS_FRAME:
      ++o.rec.wire_msgs;
      if (bytes) ++o.rec.fused_frames;
      break;
    case RS_DONE:
      ++o.done;
      ++o.rec.parts;
      o.span.last_done_us = now;
      if (o.done == o.enqueued) o.open_us += now - o.open_since_us;
      break;
  }
  if (us > 0 && stage >= RS_QUEUE && stage <= RS_DEC) keep(&o);
  if (round > max_round_) max_round_ = round;
  TryFinalizeLocked();
}

void RoundStats::TryFinalizeLocked() {
  // Oldest-first so the ring preserves round order. Two rules:
  //  - ledger-balanced rounds (workers: every enqueued partition's pull
  //    landed) finalize once a NEWER round exists — "done for now" can
  //    be mid-step (tensor A's round r completes before tensor B's
  //    round-r push is even enqueued), so a later round starting is the
  //    step boundary signal;
  //  - ledger-less rounds (servers never see RS_ENQ/RS_DONE) finalize
  //    two rounds behind the newest — one round of slack for the legal
  //    double-buffer skew between slot parities.
  for (auto it = open_.begin(); it != open_.end();) {
    const bool balanced =
        it->second.enqueued > 0 && it->second.done >= it->second.enqueued;
    const bool ledgerless = it->second.enqueued == 0;
    if ((balanced && it->first < max_round_) ||
        (ledgerless && it->first <= max_round_ - 2)) {
      FinalizeLocked(it->first);
      it = open_.erase(it);
    } else {
      ++it;
    }
  }
  // Bounded open table: force out the oldest wedged rounds.
  while (open_.size() > kOpenRounds) {
    auto it = open_.begin();
    FinalizeLocked(it->first);
    ++forced_;
    open_.erase(it);
  }
}

void RoundStats::ReduceBusy(OpenRound* o) {
  // A worker's round has both ends: nothing of it counts outside them (a
  // callback ends a little after its RS_DONE). A server's has neither.
  const int64_t lo = o->span.first_enq_us, hi = o->span.last_done_us;
  const bool ends = lo && hi;
  for (int s = 0; s < RS_STAGES; ++s) {
    std::vector<RoundInterval>& iv = o->iv[s];
    if (ends) {
      for (RoundInterval& i : iv) {
        i.start = std::min(std::max(i.start, lo), hi);
        i.end = std::min(std::max(i.end, lo), hi);
      }
    }
    o->busy.span_us[s] = MergeIntervals(&iv);
  }
  // Force-finalized with partitions still open: open to the end.
  if (o->done < o->enqueued && hi > o->open_since_us) {
    o->open_us += hi - o->open_since_us;
  }
  o->busy.feed_wait_us = ends ? hi - lo - o->open_us : 0;
}

void RoundStats::FinalizeLocked(int round) {
  OpenRound& o = open_[round];
  ReduceBusy(&o);
  const RoundRec& r = o.rec;
  ring_[ring_head_] = r;
  spans_[ring_head_] = o.span;
  busy_[ring_head_] = o.busy;
  ring_head_ = (ring_head_ + 1) % ring_cap_;
  ++ring_total_;
  PublishGaugesLocked(r);
}

void RoundStats::PublishGaugesLocked(const RoundRec& r) {
  // Per-round series on /metrics: monitor.top reads these for its
  // BOTTLENECK column without needing the /rounds endpoint. Gauges hold
  // the LAST completed round; the histogram keeps the distribution.
  BPS_METRIC_COUNTER_ADD("bps_rounds_completed_total", 1);
  BPS_METRIC_GAUGE_SET("bps_round_last", r.round);
  BPS_METRIC_GAUGE_SET("bps_round_parts", r.parts);
  BPS_METRIC_GAUGE_SET("bps_round_queue_us", r.queue_us);
  BPS_METRIC_GAUGE_SET("bps_round_comp_us", r.comp_us);
  BPS_METRIC_GAUGE_SET("bps_round_push_us", r.push_us);
  BPS_METRIC_GAUGE_SET("bps_round_sum_us", r.sum_us);
  BPS_METRIC_GAUGE_SET("bps_round_wire_ack_us",
                       r.push_us > r.sum_us ? r.push_us - r.sum_us : 0);
  BPS_METRIC_GAUGE_SET("bps_round_pull_us", r.pull_us);
  BPS_METRIC_GAUGE_SET("bps_round_dec_us", r.dec_us);
  BPS_METRIC_GAUGE_SET("bps_round_wire_bytes", r.wire_bytes);
  BPS_METRIC_GAUGE_SET("bps_round_wire_msgs", r.wire_msgs);
  BPS_METRIC_GAUGE_SET("bps_round_retries", r.retries);
  BPS_METRIC_GAUGE_SET("bps_round_parked", r.parked);
  BPS_METRIC_HISTO_OBSERVE("bps_round_wall_us", RoundWallUs(r));
}

bool RoundStats::FillWire(std::string* out) {
  if (!On() || !heartbeat_summary_on_) return false;
  std::lock_guard<std::mutex> lk(mu_);
  if (ring_total_ <= wire_sent_total_) return false;
  int64_t backlog = ring_total_ - wire_sent_total_;
  // Rounds that rotated out of the ring before a heartbeat could ship
  // them are lost to the fleet table (counted in `dropped`).
  if (backlog > static_cast<int64_t>(ring_cap_)) {
    wire_sent_total_ = ring_total_ - static_cast<int64_t>(ring_cap_);
    backlog = static_cast<int64_t>(ring_cap_);
  }
  int count = backlog > kMaxWireRecs ? kMaxWireRecs
                                     : static_cast<int>(backlog);
  RoundSummaryHdr hdr;
  hdr.magic = kRoundSummaryMagic;
  hdr.version = kRoundSummaryVersion;
  hdr.node_id = node_id_.load(std::memory_order_relaxed);
  hdr.role = role_.load(std::memory_order_relaxed);
  hdr.count = count;
  hdr.completed_total = ring_total_;
  int64_t over = ring_total_ - static_cast<int64_t>(ring_cap_);
  hdr.dropped = forced_ + (over > 0 ? over : 0);
  out->assign(reinterpret_cast<const char*>(&hdr), sizeof(hdr));
  // Oldest unsent first. ring slot of the i-th record ever finalized:
  // i % cap (head_ advanced past it).
  for (int64_t i = wire_sent_total_; i < wire_sent_total_ + count; ++i) {
    const RoundRec& r = ring_[static_cast<size_t>(i % ring_cap_)];
    out->append(reinterpret_cast<const char*>(&r), sizeof(r));
  }
  wire_sent_total_ += count;
  return true;
}

size_t RoundStats::WireSize(const void* data, size_t len) {
  if (!data || len < sizeof(RoundSummaryHdr)) return 0;
  RoundSummaryHdr hdr;
  memcpy(&hdr, data, sizeof(hdr));
  if (hdr.magic != kRoundSummaryMagic ||
      hdr.version != kRoundSummaryVersion) {
    return 0;
  }
  if (hdr.count < 0 || hdr.count > kMaxWireRecs) return 0;
  size_t need =
      sizeof(hdr) + static_cast<size_t>(hdr.count) * sizeof(RoundRec);
  return len >= need ? need : 0;
}

bool RoundStats::Ingest(const void* data, size_t len) {
  if (len < sizeof(RoundSummaryHdr)) return false;
  RoundSummaryHdr hdr;
  memcpy(&hdr, data, sizeof(hdr));
  if (hdr.magic != kRoundSummaryMagic ||
      hdr.version != kRoundSummaryVersion) {
    return false;  // unknown sender generation — interop: ignore
  }
  if (hdr.count < 0 || hdr.count > kMaxWireRecs ||
      len < sizeof(hdr) + static_cast<size_t>(hdr.count) * sizeof(RoundRec)) {
    return false;
  }
  const char* p = static_cast<const char*>(data) + sizeof(hdr);
  std::lock_guard<std::mutex> lk(mu_);
  RankState& st = fleet_[hdr.node_id];
  st.role = hdr.role;
  st.completed_total = hdr.completed_total;
  for (int i = 0; i < hdr.count; ++i) {
    RoundRec r;
    memcpy(&r, p + static_cast<size_t>(i) * sizeof(RoundRec), sizeof(r));
    st.last = r;
    ++st.updates;
    double wall = static_cast<double>(RoundWallUs(r));
    st.ewma_wall_us = st.updates == 1
                          ? wall
                          : (1.0 - kRoundEwmaAlpha) * st.ewma_wall_us +
                                kRoundEwmaAlpha * wall;
    fleet_rounds_[r.round][hdr.node_id] = r;
  }
  // Bounded fleet table: keep the last 128 rounds.
  while (fleet_rounds_.size() > 128) {
    fleet_rounds_.erase(fleet_rounds_.begin());
  }
  BPS_METRIC_COUNTER_ADD("bps_round_summaries_ingested_total", hdr.count);
  return true;
}

std::string RoundStats::SnapshotJson() {
  std::lock_guard<std::mutex> lk(mu_);
  std::string out = "{";
  out += "\"on\":" + std::string(On() ? "true" : "false");
  out += ",\"role\":" +
         std::to_string(role_.load(std::memory_order_relaxed));
  out += ",\"node_id\":" +
         std::to_string(node_id_.load(std::memory_order_relaxed));
  out += ",\"tenant\":" + std::to_string(TenantId());
  out += ",\"ring_capacity\":" + std::to_string(ring_cap_);
  out += ",\"completed_total\":" + std::to_string(ring_total_);
  int64_t over = ring_total_ - static_cast<int64_t>(ring_cap_);
  out += ",\"dropped\":" +
         std::to_string(forced_ + (over > 0 ? over : 0));
  out += ",\"last\":";
  if (ring_total_ > 0) {
    size_t last = (ring_head_ + ring_cap_ - 1) % ring_cap_;
    AppendRec(&out, ring_[last], &spans_[last], &busy_[last]);
  } else {
    out += "null";
  }
  size_t n = ring_total_ < static_cast<int64_t>(ring_cap_)
                 ? static_cast<size_t>(ring_total_)
                 : ring_cap_;
  size_t start = (ring_head_ + ring_cap_ - n) % ring_cap_;
  out += ",\"rounds\":[";
  for (size_t i = 0; i < n; ++i) {
    if (i) out += ",";
    size_t slot = (start + i) % ring_cap_;
    AppendRec(&out, ring_[slot], &spans_[slot], &busy_[slot]);
  }
  out += "]";
  out += ",\"fleet\":{";
  bool first = true;
  for (const auto& kv : fleet_) {
    if (!first) out += ",";
    first = false;
    out += "\"" + std::to_string(kv.first) + "\":{";
    out += "\"role\":" + std::to_string(kv.second.role);
    auto tit = node_tenant_.find(kv.first);
    out += ",\"tenant\":" +
           std::to_string(tit == node_tenant_.end() ? 0 : tit->second);
    out += ",\"completed_total\":" +
           std::to_string(kv.second.completed_total);
    out += ",\"updates\":" + std::to_string(kv.second.updates);
    char e[48];
    snprintf(e, sizeof(e), ",\"ewma_wall_us\":%.1f",
             kv.second.ewma_wall_us);
    out += e;
    out += ",\"last\":";
    AppendRec(&out, kv.second.last);
    out += "}";
  }
  out += "},\"fleet_rounds\":{";
  first = true;
  for (const auto& rkv : fleet_rounds_) {
    if (!first) out += ",";
    first = false;
    out += "\"" + std::to_string(rkv.first) + "\":{";
    bool f2 = true;
    for (const auto& nkv : rkv.second) {
      if (!f2) out += ",";
      f2 = false;
      out += "\"" + std::to_string(nkv.first) + "\":";
      AppendRec(&out, nkv.second);
    }
    out += "}";
  }
  out += "}}";
  return out;
}

}  // namespace bps
