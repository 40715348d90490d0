// BlockMax-WAND BM25 scoring engine.
//
// Reference: adapters/repos/db/inverted/bm25_searcher_block.go — Weaviate's
// BlockMax-WAND over block-compressed postings (StrategyInverted segments).
// This is the CPU-side sparse complement to the TPU dense path: posting
// lists per (property, term) with per-block max-tf upper bounds, WAND
// pivoting, and a top-k heap. Exposed as a C ABI for ctypes.
//
// Scoring matches the Python tier exactly: the caller passes per-query-term
// weight w = boost * idf and the property's current avgdl; the engine
// computes  w * tf * (k1+1) / (tf + k1*(1-b + b*dl/avgdl)).
//
// Upper bounds used for skipping (both monotone in tf, valid for any
// avgdl > 0 since dl/avgdl >= 0):
//   term bound   = w * (k1+1) * maxtf / (maxtf + k1*(1-b))
//   block bound  = same formula with the block's max tf.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <queue>
#include <unordered_map>
#include <unordered_set>
#include <vector>

namespace {

constexpr uint32_t BLOCK = 128;

struct Posting {
    int64_t doc;
    uint32_t tf;
    uint32_t dl;  // document length in the posting's property
};

struct PostingList {
    std::vector<Posting> entries;  // sorted by doc id
    std::vector<uint32_t> block_max_tf;
    uint32_t max_tf = 0;
    bool dirty = false;
    uint64_t purge_gen = 0;  // tombstone generation last purged at
};

struct Index {
    float k1, b;
    std::unordered_map<uint64_t, PostingList> postings;  // term id -> list
    std::unordered_set<int64_t> tombstones;
    uint64_t tomb_gen = 0;  // bumped per remove; lists purge lazily

    PostingList* find(uint64_t term) {
        auto it = postings.find(term);
        return it == postings.end() ? nullptr : &it->second;
    }

    // purge tombstoned docs and rebuild block maxes — dead high-tf docs
    // must not keep upper bounds loose (and memory must track live docs)
    void finalize(PostingList& pl) {
        if (pl.purge_gen != tomb_gen) {
            size_t before = pl.entries.size();
            pl.entries.erase(
                std::remove_if(pl.entries.begin(), pl.entries.end(),
                               [&](const Posting& p) {
                                   return tombstones.count(p.doc) != 0;
                               }),
                pl.entries.end());
            if (pl.entries.size() != before) pl.dirty = true;
            pl.purge_gen = tomb_gen;
        }
        if (!pl.dirty) return;
        std::sort(pl.entries.begin(), pl.entries.end(),
                  [](const Posting& a, const Posting& b) {
                      return a.doc < b.doc;
                  });
        pl.block_max_tf.clear();
        pl.max_tf = 0;
        for (size_t i = 0; i < pl.entries.size(); ++i) {
            if (i % BLOCK == 0) pl.block_max_tf.push_back(0);
            pl.block_max_tf.back() = std::max(pl.block_max_tf.back(),
                                              pl.entries[i].tf);
            pl.max_tf = std::max(pl.max_tf, pl.entries[i].tf);
        }
        pl.dirty = false;
    }
};

struct Cursor {
    PostingList* pl;
    size_t pos = 0;
    float weight;   // boost * idf
    float avgdl;
    float term_bound;
    uint32_t group = 0;  // distinct-token group (min-match rule)

    bool done() const { return pos >= pl->entries.size(); }
    int64_t doc() const { return pl->entries[pos].doc; }

    // advance to first posting with doc >= target (galloping + binary)
    void seek(int64_t target) {
        size_t lo = pos, step = 1;
        size_t n = pl->entries.size();
        size_t hi = pos;
        while (hi < n && pl->entries[hi].doc < target) {
            lo = hi;
            hi += step;
            step <<= 1;
        }
        hi = std::min(hi, n);
        pos = std::lower_bound(
                  pl->entries.begin() + lo, pl->entries.begin() + hi, target,
                  [](const Posting& p, int64_t t) { return p.doc < t; }) -
              pl->entries.begin();
    }

    float block_bound(float k1, float b) const {
        uint32_t btf = pl->block_max_tf[pos / BLOCK];
        return weight * btf * (k1 + 1.0f) / (btf + k1 * (1.0f - b));
    }
};

float score_posting(const Index* ix, const Posting& p, float weight,
                    float avgdl) {
    float denom = p.tf + ix->k1 * (1.0f - ix->b +
                                   ix->b * p.dl / std::max(avgdl, 1e-9f));
    return weight * p.tf * (ix->k1 + 1.0f) / std::max(denom, 1e-9f);
}

}  // namespace

extern "C" {

void* bm25_new(float k1, float b) {
    auto* ix = new Index();
    ix->k1 = k1;
    ix->b = b;
    return ix;
}

// live config update (schema PUT): scoring params apply to the next
// search — postings and block maxima are tf-based, so no rebuild needed
void bm25_set_params(void* h, float k1, float b) {
    auto* ix = static_cast<Index*>(h);
    ix->k1 = k1;
    ix->b = b;
}

void bm25_free(void* h) { delete static_cast<Index*>(h); }

// add a write batch's documents of one property-term-id space in one
// call: document i carries term_ids[offsets[i] .. offsets[i+1]) with the
// matching tfs. term_ids are 64-bit ids the caller derives from
// (property, term).
void bm25_add_docs(void* h, uint64_t n_docs, const int64_t* docs,
                   const uint32_t* doc_lens, const uint64_t* offsets,
                   const uint64_t* term_ids, const uint32_t* tfs) {
    auto* ix = static_cast<Index*>(h);
    for (uint64_t i = 0; i < n_docs; ++i) {
        ix->tombstones.erase(docs[i]);
        for (uint64_t j = offsets[i]; j < offsets[i + 1]; ++j) {
            auto& pl = ix->postings[term_ids[j]];
            pl.entries.push_back({docs[i], tfs[j], doc_lens[i]});
            pl.dirty = true;
        }
    }
}

// bulk-append one term's posting list (snapshot load path): docs may be
// pre-sorted; lists are finalized lazily at first search either way.
void bm25_add_term(void* h, uint64_t term_id, const int64_t* docs,
                   const uint32_t* tfs, const uint32_t* dls, uint64_t n) {
    auto* ix = static_cast<Index*>(h);
    auto& pl = ix->postings[term_id];
    pl.entries.reserve(pl.entries.size() + n);
    for (uint64_t i = 0; i < n; ++i) {
        pl.entries.push_back({docs[i], tfs[i], dls[i]});
    }
    pl.dirty = true;
}

void bm25_remove_doc(void* h, int64_t doc) {
    auto* ix = static_cast<Index*>(h);
    if (ix->tombstones.insert(doc).second) ix->tomb_gen++;
}

// drop one term's posting list entirely — the eviction/invalidation
// primitive for the bounded term cache the segment-resident inverted
// index keeps over its LSM postings buckets
void bm25_drop_term(void* h, uint64_t term_id) {
    static_cast<Index*>(h)->postings.erase(term_id);
}

// purge all tombstoned entries from every posting list, then drop the
// tombstone set (callable periodically from the host on delete-heavy flows)
void bm25_compact(void* h) {
    auto* ix = static_cast<Index*>(h);
    for (auto& kv : ix->postings) ix->finalize(kv.second);
    ix->tombstones.clear();
}

uint64_t bm25_posting_len(void* h, uint64_t term_id) {
    auto* pl = static_cast<Index*>(h)->find(term_id);
    return pl ? pl->entries.size() : 0;
}

// WAND top-k with optional allow-list. Query: n terms with weights
// (= boost*idf) and the property avgdl per term. allow: byte-per-doc
// bitmap (nullptr = no filter; docs >= allow_len are excluded when a
// filter is present — the filter defines the candidate universe). The
// filter only removes candidates, so WAND/BMW upper bounds stay sound.
// Returns number of results written (<= k), descending score; ties by
// ascending doc id. term_groups (may be null) maps each query term to
// its distinct-token group; a doc enters the top-k only when it
// matches >= min_match distinct groups (reference
// minimumOrTokensMatch / operator AND; groups exist because BM25F
// fans one token out across properties and it must count once).
uint32_t bm25_search_min_match(void* h, const uint64_t* term_ids,
                               const float* weights, const float* avgdls,
                               const uint32_t* term_groups,
                               uint32_t min_match,
                               uint32_t n_terms, uint32_t k,
                               const uint8_t* allow, uint64_t allow_len,
                               int64_t* out_docs, float* out_scores) {
    auto* ix = static_cast<Index*>(h);
    std::vector<Cursor> cursors;
    cursors.reserve(n_terms);
    uint32_t n_group_slots = 1;
    for (uint32_t i = 0; i < n_terms; ++i) {
        PostingList* pl = ix->find(term_ids[i]);
        if (!pl) continue;
        ix->finalize(*pl);
        if (pl->entries.empty()) continue;
        Cursor c;
        c.pl = pl;
        c.weight = weights[i];
        c.avgdl = avgdls[i];
        c.term_bound = weights[i] * pl->max_tf * (ix->k1 + 1.0f) /
                       (pl->max_tf + ix->k1 * (1.0f - ix->b));
        c.group = term_groups ? term_groups[i] : i;
        if (c.group + 1 > n_group_slots) n_group_slots = c.group + 1;
        cursors.push_back(c);
    }
    if (cursors.empty() || k == 0) return 0;
    std::vector<uint8_t> seen_groups;
    if (min_match > 1) seen_groups.resize(n_group_slots, 0);

    // min-heap of (score, -doc) keeping the current top-k
    using Entry = std::pair<float, int64_t>;
    auto cmp = [](const Entry& a, const Entry& b) {
        if (a.first != b.first) return a.first > b.first;
        return a.second < b.second;  // larger doc evicted first on ties
    };
    std::priority_queue<Entry, std::vector<Entry>, decltype(cmp)> heap(cmp);
    float threshold = -1.0f;

    std::vector<Cursor*> order;
    for (auto& c : cursors) order.push_back(&c);

    while (true) {
        // sort live cursors by current doc id (small vector: insertion ok)
        order.erase(std::remove_if(order.begin(), order.end(),
                                   [](Cursor* c) { return c->done(); }),
                    order.end());
        if (order.empty()) break;
        std::sort(order.begin(), order.end(), [](Cursor* a, Cursor* b) {
            return a->doc() < b->doc();
        });
        // find pivot: first cursor where cumulative term bounds exceed
        // the threshold
        float acc = 0.0f;
        size_t pivot_i = order.size();
        for (size_t i = 0; i < order.size(); ++i) {
            acc += order[i]->term_bound;
            if (acc > threshold) {
                pivot_i = i;
                break;
            }
        }
        if (pivot_i == order.size()) break;  // no doc can beat threshold
        int64_t pivot_doc = order[pivot_i]->doc();

        if (order[0]->doc() != pivot_doc) {
            // block-max refinement over the prefix cursors' current blocks
            float block_acc = 0.0f;
            int64_t min_block_last = INT64_MAX;
            for (size_t i = 0; i <= pivot_i; ++i) {
                Cursor* c = order[i];
                block_acc += c->block_bound(ix->k1, ix->b);
                size_t last =
                    std::min((c->pos / BLOCK + 1) * BLOCK,
                             c->pl->entries.size()) - 1;
                min_block_last =
                    std::min(min_block_last, c->pl->entries[last].doc);
            }
            if (block_acc <= threshold) {
                // Sound skip (Ding & Suel BMW): for any doc d with
                // order[0].doc <= d < min(min_block_last+1, pivot_doc),
                // only prefix cursors can hold d and each entry lies in
                // its current block, so score(d) <= block_acc <= theta.
                // The pivot doc itself is NOT skipped (suffix cursors may
                // contribute to it).
                Cursor* c = order[0];
                int64_t target =
                    std::min(min_block_last + 1, pivot_doc);
                c->seek(std::max(target, c->doc() + 1));
            } else {
                // advance cursors before the pivot up to the pivot doc
                for (size_t i = 0; i < pivot_i; ++i) {
                    if (order[i]->doc() < pivot_doc) {
                        order[i]->seek(pivot_doc);
                    }
                }
            }
            continue;
        }

        {
            // all cursors up to pivot aligned: score the doc fully
            bool allowed =
                allow == nullptr ||
                (pivot_doc >= 0 && (uint64_t)pivot_doc < allow_len &&
                 allow[pivot_doc]);
            if (allowed && !ix->tombstones.count(pivot_doc)) {
                float s = 0.0f;
                uint32_t gcount = 0;  // distinct-token groups hit (exact)
                if (min_match > 1)
                    std::fill(seen_groups.begin(), seen_groups.end(), 0);
                for (Cursor* c : order) {
                    if (c->done() || c->doc() != pivot_doc) continue;
                    s += score_posting(ix, c->pl->entries[c->pos], c->weight,
                                       c->avgdl);
                    if (min_match > 1 && !seen_groups[c->group]) {
                        seen_groups[c->group] = 1;
                        ++gcount;
                    }
                }
                if (min_match > 1 && gcount < min_match) {
                    for (Cursor* c : order) {
                        if (!c->done() && c->doc() == pivot_doc)
                            c->seek(pivot_doc + 1);
                    }
                    continue;
                }
                if ((uint32_t)heap.size() < k) {
                    heap.push({s, pivot_doc});
                    if ((uint32_t)heap.size() == k)
                        threshold = heap.top().first;
                } else if (s > threshold ||
                           (s == threshold && pivot_doc < heap.top().second)) {
                    heap.pop();
                    heap.push({s, pivot_doc});
                    threshold = heap.top().first;
                }
            }
            for (Cursor* c : order) {
                if (!c->done() && c->doc() == pivot_doc) c->seek(pivot_doc + 1);
            }
        }
    }

    uint32_t n = (uint32_t)heap.size();
    for (uint32_t i = n; i-- > 0;) {
        out_docs[i] = heap.top().second;
        out_scores[i] = heap.top().first;
        heap.pop();
    }
    return n;
}

uint32_t bm25_search_filtered(void* h, const uint64_t* term_ids,
                              const float* weights, const float* avgdls,
                              uint32_t n_terms, uint32_t k,
                              const uint8_t* allow, uint64_t allow_len,
                              int64_t* out_docs, float* out_scores) {
    return bm25_search_min_match(h, term_ids, weights, avgdls, nullptr, 1,
                                 n_terms, k, allow, allow_len, out_docs,
                                 out_scores);
}

uint32_t bm25_search(void* h, const uint64_t* term_ids, const float* weights,
                     const float* avgdls, uint32_t n_terms, uint32_t k,
                     int64_t* out_docs, float* out_scores) {
    return bm25_search_filtered(h, term_ids, weights, avgdls, n_terms, k,
                                nullptr, 0, out_docs, out_scores);
}

// exact (non-WAND) scoring of specific docs — used by hybrid rescoring
void bm25_score_docs(void* h, const uint64_t* term_ids, const float* weights,
                     const float* avgdls, uint32_t n_terms,
                     const int64_t* docs, uint32_t n_docs, float* out) {
    auto* ix = static_cast<Index*>(h);
    std::memset(out, 0, n_docs * sizeof(float));
    for (uint32_t t = 0; t < n_terms; ++t) {
        PostingList* pl = ix->find(term_ids[t]);
        if (!pl) continue;
        ix->finalize(*pl);
        for (uint32_t d = 0; d < n_docs; ++d) {
            if (ix->tombstones.count(docs[d])) continue;
            auto it = std::lower_bound(
                pl->entries.begin(), pl->entries.end(), docs[d],
                [](const Posting& p, int64_t x) { return p.doc < x; });
            if (it != pl->entries.end() && it->doc == docs[d]) {
                out[d] += score_posting(ix, *it, weights[t], avgdls[t]);
            }
        }
    }
}

}  // extern "C"
