// Native host-side data plane for loongcollector_tpu.
//
// The reference implements these paths in C++ (SURVEY.md §2.1/§2.3):
//   - chunk → line spans         (LogFileReader / ProcessorSplitLogString)
//   - arena → fixed device rows  (the TPU batch staging copy)
//   - columnar spans → SLS protobuf wire bytes
//     (hand-rolled LogGroupSerializer, core/protobuf/sls/)
//
// Python loads this via ctypes (loongcollector_tpu/native.py) and falls back
// to numpy/pure-Python implementations when the library is absent.
//
// Build: make -C native   (g++ -O3 -shared -fPIC)

#include <algorithm>
#include <cerrno>
#include <cstdint>
#include <cstring>
#include <ctime>
#include <fcntl.h>
#include <new>
#include <unistd.h>
#if defined(__x86_64__)
#include <immintrin.h>
#endif

extern "C" {

// ---------------------------------------------------------------------------
// Line splitting: returns number of line spans written.
// Keeps empty interior lines; drops the empty tail after a trailing sep.
// out_offsets/out_lengths must hold at least (count of sep)+1 entries.
// ---------------------------------------------------------------------------
int64_t lct_split_lines(const uint8_t* data, int64_t len, uint8_t sep,
                        int64_t base_offset, int32_t* out_offsets,
                        int32_t* out_lengths) {
    int64_t n = 0;
    int64_t start = 0;
    const uint8_t* p = data;
    while (start < len) {
        const uint8_t* hit =
            static_cast<const uint8_t*>(memchr(p + start, sep, len - start));
        int64_t end = hit ? (hit - p) : len;
        out_offsets[n] = static_cast<int32_t>(base_offset + start);
        out_lengths[n] = static_cast<int32_t>(end - start);
        ++n;
        start = end + 1;
    }
    // interior empty lines between consecutive separators
    // (handled naturally: start==end gives length 0)
    return n;
}

// ---------------------------------------------------------------------------
// Row packing: gather event byte ranges into a zero-padded [B, L] matrix.
// Rows beyond n are zeroed by the caller (numpy allocates zeroed).
// ---------------------------------------------------------------------------
void lct_pack_rows(const uint8_t* arena, int64_t arena_len,
                   const int64_t* offsets, const int32_t* lengths, int64_t n,
                   int64_t L, uint8_t* out_rows) {
    for (int64_t i = 0; i < n; ++i) {
        int64_t off = offsets[i];
        int64_t len = lengths[i];
        if (len < 0) len = 0;  // absent field spans (-1) pack as empty rows
        if (len > L) len = L;
        if (off < 0 || off >= arena_len) {
            len = 0;
        } else if (off + len > arena_len) {
            len = arena_len - off;
        }
        uint8_t* dst = out_rows + i * L;
        if (len > 0) memcpy(dst, arena + off, static_cast<size_t>(len));
        if (len < L) memset(dst + len, 0, static_cast<size_t>(L - len));
    }
}

// ---------------------------------------------------------------------------
// The timestamp column of one group in one call: the per-group work of
// processor/parse_timestamp.py's column path (the plan, the semantics and
// the numpy twin live there).  For each row with the field (length >= 0;
// offsets and lengths are int32 at a byte stride, as ColumnarLogs stores a
// column of a span matrix): the `width` bytes at its offset are looked up
// in `table[column * 256 + byte]` (the byte's worth, top bit set: not in the
// column's class) and summed by `weights[column * 2 + {minute's key,
// second}]`; the key is looked up in the sorted memo (keys → epoch seconds
// of the minute's second 0) and seconds + second is stored into
// `timestamps` in place when it is >= 0.
//   rest_rows     rows the plan cannot prove (width, class, a span that
//                 leaves the arena, a minute whose seconds are negative):
//                 the caller's per-row path has them
//   pending_rows  rows whose minute is not in the memo: nothing stored;
//   missing_keys  their distinct keys, ascending.  The caller asks the
//                 standard library, grows the memo and calls again with
//                 pending_rows as `rows_in`
//   counts        present rows seen, rows stored, and the lengths of the
//                 three outputs (each output holds `n` entries)
// `rows_in` NULL: every row of the group, and a group with fewer than
// `min_present` present rows is declined untouched (-1).  Returns 0.
// ---------------------------------------------------------------------------
int64_t lct_timestamp_column(
        const uint8_t* arena, int64_t arena_len,
        const uint8_t* offs, int64_t offs_stride,
        const uint8_t* lens, int64_t lens_stride, int64_t n,
        const int64_t* rows_in, int64_t n_rows_in, int64_t min_present,
        int64_t width, const uint8_t* table, const int64_t* weights,
        const int64_t* memo_keys, const int64_t* memo_seconds,
        int64_t memo_len, int64_t* timestamps,
        int64_t* rest_rows, int64_t* pending_rows, int64_t* missing_keys,
        int64_t* counts) {
    auto load32 = [](const uint8_t* base, int64_t stride, int64_t i) {
        int32_t v;
        memcpy(&v, base + i * stride, sizeof v);
        return v;
    };
    const int64_t todo = rows_in ? n_rows_in : n;
    if (!rows_in && min_present > 0) {
        int64_t present = 0;
        for (int64_t i = 0; i < n && present < min_present; ++i)
            present += load32(lens, lens_stride, i) >= 0;
        if (present < min_present) return -1;
    }
    int64_t present = 0, stored = 0, n_rest = 0, n_pending = 0;
    int64_t last = 0;       // the memo entry the row before hit
    // a group's arena is cold by the time its stamps are read (512 KiB a
    // group, a few groups in flight): ask for the stamp of the row
    // `kAhead` on, two cache lines at most
    constexpr int64_t kAhead = 16;
    for (int64_t k = 0; k < todo; ++k) {
        if (k + kAhead < todo) {
            const int64_t a = rows_in ? rows_in[k + kAhead] : k + kAhead;
            if (a >= 0 && a < n) {
                const int64_t ahead = load32(offs, offs_stride, a);
                if (ahead >= 0 && ahead + width <= arena_len) {
                    __builtin_prefetch(arena + ahead);
                    __builtin_prefetch(arena + ahead + width - 1);
                }
            }
        }
        const int64_t r = rows_in ? rows_in[k] : k;
        if (r < 0 || r >= n) continue;
        const int32_t len = load32(lens, lens_stride, r);
        if (len < 0) continue;
        ++present;
        const int64_t off = load32(offs, offs_stride, r);
        if (len != width || off < 0 || off + width > arena_len) {
            rest_rows[n_rest++] = r;
            continue;
        }
        const uint8_t* p = arena + off;
        int64_t key = 0, second = 0;
        uint8_t outside = 0;
        for (int64_t c = 0; c < width; ++c) {
            const uint8_t worth = table[c * 256 + p[c]];
            outside |= worth;
            key += worth * weights[c * 2];
            second += worth * weights[c * 2 + 1];
        }
        if (outside & 0x80) {
            rest_rows[n_rest++] = r;
            continue;
        }
        if (last >= memo_len || memo_keys[last] != key) {
            const int64_t* hit =
                std::lower_bound(memo_keys, memo_keys + memo_len, key);
            if (hit == memo_keys + memo_len || *hit != key) {
                missing_keys[n_pending] = key;
                pending_rows[n_pending++] = r;
                continue;
            }
            last = hit - memo_keys;
        }
        const int64_t ts = memo_seconds[last] + second;
        if (ts < 0) {
            rest_rows[n_rest++] = r;
            continue;
        }
        timestamps[r] = ts;
        ++stored;
    }
    std::sort(missing_keys, missing_keys + n_pending);
    counts[0] = present;
    counts[1] = stored;
    counts[2] = n_rest;
    counts[3] = n_pending;
    counts[4] = std::unique(missing_keys, missing_keys + n_pending)
        - missing_keys;
    return 0;
}

// ---------------------------------------------------------------------------
// SLS LogGroup wire serialization from columnar spans.
//
// Wire schema (public sls_logs.proto):
//   Log      { uint32 Time = 1; repeated Content Contents = 2; }
//   Content  { string Key = 1; string Value = 2; }
//   LogGroup { repeated Log Logs = 1; ... }
//
// Inputs: shared arena; per-event timestamps; F fields, each with a key
// (concatenated in keys_blob with key_lens) and per-event (offset,len)
// spans (len < 0 ⇒ absent).
// Returns bytes written, or -(needed) if out_cap is too small (caller
// reallocates and retries; needed is exact).
// ---------------------------------------------------------------------------

static inline int varint_size(uint64_t v) {
    int s = 1;
    while (v >= 0x80) { v >>= 7; ++s; }
    return s;
}

static inline uint8_t* put_varint(uint8_t* p, uint64_t v) {
    while (v >= 0x80) { *p++ = static_cast<uint8_t>(v) | 0x80; v >>= 7; }
    *p++ = static_cast<uint8_t>(v);
    return p;
}

// Short-copy with 16-byte over-write: log fields are mostly 2–20 bytes and
// a libc memcpy call per field dominates the serializer.  Requires 16 bytes
// of readable slack after src and writable slack after dst (the caller
// over-allocates; src slack is bounds-checked by the caller).
static inline uint8_t* put_bytes_fast(uint8_t* p, const uint8_t* s,
                                      int64_t k) {
    if (k <= 16) {
        uint64_t a, b;
        memcpy(&a, s, 8);
        memcpy(&b, s + 8, 8);
        memcpy(p, &a, 8);
        memcpy(p + 8, &b, 8);
        return p + k;
    }
    memcpy(p, s, static_cast<size_t>(k));
    return p + k;
}

// Strided span layout: element (f, i) lives at f*sf + i*si.  Field-major
// [F, n] ⇒ (sf=n, si=1); event-major [n, F] ⇒ (sf=1, si=F) — the parse
// kernels emit [n, C] matrices, and serializing them directly skips a
// transpose + stack per group.
int64_t lct_sls_serialize_strided(
        const uint8_t* arena, int64_t arena_len, const int64_t* timestamps,
        int64_t n, int64_t F, const uint8_t* keys_blob,
        const int32_t* key_lens, const int32_t* field_offs,
        const int32_t* field_lens, int64_t sf, int64_t si, uint8_t* out,
        int64_t out_cap) {
    // key prefix offsets into keys_blob
    int64_t key_starts[64];
    if (F > 64) return -1;
    int64_t acc = 0;
    for (int64_t f = 0; f < F; ++f) { key_starts[f] = acc; acc += key_lens[f]; }

    // a span is emitted iff it passes BOTH the absence and bounds checks —
    // the predicate must be identical in the size and write passes or the
    // length prefixes desynchronise from the written bytes
    auto span_ok = [&](int64_t idx) -> bool {
        int32_t vlen = field_lens[idx];
        if (vlen < 0) return false;
        int32_t voff = field_offs[idx];
        return voff >= 0 && static_cast<int64_t>(voff) + vlen <= arena_len;
    };

    // per-field key-part size is constant across events
    int32_t key_part[64];
    for (int64_t f = 0; f < F; ++f)
        key_part[f] = 1 + varint_size(key_lens[f]) + key_lens[f] + 1;

    // per-field constant wire prefix: 0x0a klen <key> 0x12 — one cache-hot
    // copy per field instead of three stores + a libc memcpy
    uint8_t keyhdr[64][112];
    int32_t keyhdr_len[64];
    for (int64_t f = 0; f < F; ++f) {
        int32_t klen = key_lens[f];
        if (klen + varint_size(klen) + 2 > 96) {
            keyhdr_len[f] = -1;            // oversize key: slow path
            continue;
        }
        uint8_t* q = keyhdr[f];
        *q++ = 0x0a;                       // Content.Key
        q = put_varint(q, klen);
        memcpy(q, keys_blob + key_starts[f], klen);
        q += klen;
        *q++ = 0x12;                       // Content.Value tag
        keyhdr_len[f] = (int32_t)(q - keyhdr[f]);
    }

    // Single pass: reserve two bytes for each Log's body-length varint and
    // patch it once the body is written (bodies of 128..16383 bytes — the
    // norm for log events — need exactly two; the off sizes memmove the
    // just-written body by ±, which short bodies make cheap).  This
    // replaces the old size-then-write double walk over every span.
    // On overflow the exact total is computed by a (rare) sizing walk and
    // returned as -(needed) for the caller's retry.
    const uint8_t* out_end = out + out_cap;
    uint8_t* p = out;
    bool overflow = false;
    for (int64_t i = 0; i < n && !overflow; ++i) {
        uint64_t ts = static_cast<uint64_t>(timestamps[i]) & 0xFFFFFFFFu;
        if (p + 16 > out_end) { overflow = true; break; }
        *p++ = 0x0a;                       // LogGroup.Logs
        uint8_t* lenpos = p;
        p += 2;                            // reserved body-length varint
        uint8_t* body_start = p;
        *p++ = 0x08;                       // Log.Time
        p = put_varint(p, ts);
        int64_t base = i * si;
        for (int64_t f = 0; f < F; ++f) {
            int64_t idx = base + f * sf;
            if (!span_ok(idx)) continue;
            int32_t vlen = field_lens[idx];
            int32_t voff = field_offs[idx];
            int64_t content = key_part[f] + varint_size(vlen) + vlen;
            if (p + content + 24 > out_end) { overflow = true; break; }
            *p++ = 0x12;                   // Log.Contents
            p = put_varint(p, content);
            int32_t kh = keyhdr_len[f];
            if (kh >= 0) {
                p = put_bytes_fast(p, keyhdr[f], kh);
            } else {
                int32_t klen = key_lens[f];
                *p++ = 0x0a;               // Content.Key
                p = put_varint(p, klen);
                memcpy(p, keys_blob + key_starts[f], klen);
                p += klen;
                *p++ = 0x12;               // Content.Value
            }
            p = put_varint(p, vlen);
            if ((int64_t)voff + vlen + 16 <= arena_len) {
                p = put_bytes_fast(p, arena + voff, vlen);
            } else {
                memcpy(p, arena + voff, vlen);
                p += vlen;
            }
        }
        if (overflow) break;
        int64_t body = p - body_start;
        if (body < 0x80) {
            lenpos[0] = (uint8_t)body;
            memmove(lenpos + 1, body_start, (size_t)body);
            p -= 1;
        } else if (body < 0x4000) {
            lenpos[0] = (uint8_t)(body & 0x7F) | 0x80;
            lenpos[1] = (uint8_t)(body >> 7);
        } else {
            int extra = varint_size((uint64_t)body) - 2;
            if (p + extra + 16 > out_end) { overflow = true; break; }
            memmove(lenpos + 2 + extra, body_start, (size_t)body);
            put_varint(lenpos, (uint64_t)body);
            p += extra;
        }
    }
    if (overflow) {
        // exact resize request (same emission predicate as the writer)
        int64_t total = 0;
        for (int64_t i = 0; i < n; ++i) {
            uint64_t ts = static_cast<uint64_t>(timestamps[i]) & 0xFFFFFFFFu;
            int64_t body = 1 + varint_size(ts);
            int64_t base = i * si;
            for (int64_t f = 0; f < F; ++f) {
                int64_t idx = base + f * sf;
                if (!span_ok(idx)) continue;
                int32_t vlen = field_lens[idx];
                int64_t content = key_part[f] + varint_size(vlen) + vlen;
                body += 1 + varint_size(content) + content;
            }
            total += 1 + varint_size(body) + body;
        }
        return -(total + 32);
    }
    return p - out;
}

// legacy field-major entry point
int64_t lct_sls_serialize(const uint8_t* arena, int64_t arena_len,
                          const int64_t* timestamps, int64_t n,
                          int64_t F,
                          const uint8_t* keys_blob, const int32_t* key_lens,
                          const int32_t* field_offs,  // [F * n]
                          const int32_t* field_lens,  // [F * n]
                          uint8_t* out, int64_t out_cap) {
    return lct_sls_serialize_strided(arena, arena_len, timestamps, n, F,
                                     keys_blob, key_lens, field_offs,
                                     field_lens, n, 1, out, out_cap);
}

}  // extern "C"

// ---------------------------------------------------------------------------
// NDJSON serialization from columnar spans (loongshard zero-copy fast path).
//
// One JSON object per event, byte-identical to CPython's
// json.dumps(obj, ensure_ascii=False) with default separators:
//   <prefix>[", "]"<ts>": N, "key": "value", ...}<suffix>
//
// * prefix is the caller-built row head: '{' plus the JSON-encoded group
//   tags, WITHOUT a trailing separator (prefix_members says whether it
//   already holds members);
// * key_frags are caller-built '"key": "' fragments (keys pre-escaped);
// * values are arena spans escaped inline the way json.dumps does it
//   (\" \\ \b \f \n \r \t, \u00XX for remaining control bytes); bytes
//   >= 0x80 pass through unchanged — the CALLER guarantees the span is
//   valid UTF-8 (rows that are not must stay on the Python fallback to
//   match the codec's replacement semantics);
// * ts_mode: 0 = no timestamp member, 1 = decimal epoch, 2 = ISO-8601
//   UTC ("%Y-%m-%dT%H:%M:%SZ"); ts_first: 1 = right after the prefix
//   (JsonSerializer layout), 0 = appended after the fields (the
//   setdefault layout of the NDJSON flushers).
//
// Spans use the same strided layout as lct_sls_serialize_strided.
// Returns bytes written, or -1 when out_cap cannot hold a row (callers
// allocate the worst-case bound up front, so -1 means "fall back").
// ---------------------------------------------------------------------------

namespace {

// JSON string-escape class per byte: 0 = emit as-is (includes >= 0x80;
// see the UTF-8 caller contract), 1 = two-char escape, 2 = \u00XX
inline const uint8_t* json_escape_class() {
    static uint8_t cls[256];
    static bool init = false;
    if (!init) {
        for (int i = 0; i < 0x20; ++i) cls[i] = 2;
        cls['\b'] = cls['\t'] = cls['\n'] = cls['\f'] = cls['\r'] = 1;
        cls['"'] = cls['\\'] = 1;
        init = true;
    }
    return cls;
}

inline uint8_t* put_json_escaped(uint8_t* p, const uint8_t* s, int64_t k,
                                 const uint8_t* cls) {
    static const char hex[] = "0123456789abcdef";
    int64_t run = 0;
    for (int64_t j = 0; j < k; ++j) {
        uint8_t c = s[j];
        if (cls[c] == 0) { ++run; continue; }
        if (run) { memcpy(p, s + j - run, (size_t)run); p += run; run = 0; }
        if (cls[c] == 1) {
            *p++ = '\\';
            switch (c) {
                case '\b': *p++ = 'b'; break;
                case '\t': *p++ = 't'; break;
                case '\n': *p++ = 'n'; break;
                case '\f': *p++ = 'f'; break;
                case '\r': *p++ = 'r'; break;
                default:   *p++ = c;   break;  // '"' and '\\'
            }
        } else {
            *p++ = '\\'; *p++ = 'u'; *p++ = '0'; *p++ = '0';
            *p++ = hex[c >> 4]; *p++ = hex[c & 0xF];
        }
    }
    if (run) { memcpy(p, s + k - run, (size_t)run); p += run; }
    return p;
}

inline uint8_t* put_decimal_i64(uint8_t* p, int64_t v) {
    if (v < 0) { *p++ = '-'; }
    uint64_t u = v < 0 ? (uint64_t)(-(v + 1)) + 1 : (uint64_t)v;
    char tmp[20];
    int k = 0;
    do { tmp[k++] = (char)('0' + u % 10); u /= 10; } while (u);
    while (k) *p++ = tmp[--k];
    return p;
}

inline uint8_t* put_2d(uint8_t* p, int v) {
    *p++ = (uint8_t)('0' + v / 10);
    *p++ = (uint8_t)('0' + v % 10);
    return p;
}

// epoch seconds → "YYYY-MM-DDTHH:MM:SSZ" (proleptic Gregorian, UTC) —
// the civil_from_days algorithm, matching Python's
// datetime.fromtimestamp(ts, tz=utc).strftime("%Y-%m-%dT%H:%M:%SZ")
inline uint8_t* put_iso8601(uint8_t* p, int64_t ts) {
    int64_t days = ts / 86400;
    int64_t rem = ts % 86400;
    if (rem < 0) { rem += 86400; --days; }
    int64_t z = days + 719468;
    int64_t era = (z >= 0 ? z : z - 146096) / 146097;
    int64_t doe = z - era * 146097;
    int64_t yoe = (doe - doe / 1460 + doe / 36524 - doe / 146096) / 365;
    int64_t y = yoe + era * 400;
    int64_t doy = doe - (365 * yoe + yoe / 4 - yoe / 100);
    int64_t mp = (5 * doy + 2) / 153;
    int64_t d = doy - (153 * mp + 2) / 5 + 1;
    int64_t m = mp < 10 ? mp + 3 : mp - 9;
    if (m <= 2) ++y;
    p = put_decimal_i64(p, y);
    *p++ = '-'; p = put_2d(p, (int)m);
    *p++ = '-'; p = put_2d(p, (int)d);
    *p++ = 'T'; p = put_2d(p, (int)(rem / 3600));
    *p++ = ':'; p = put_2d(p, (int)((rem / 60) % 60));
    *p++ = ':'; p = put_2d(p, (int)(rem % 60));
    *p++ = 'Z';
    return p;
}

}  // namespace

extern "C" {

int64_t lct_ndjson_serialize(
        const uint8_t* arena, int64_t arena_len, const int64_t* timestamps,
        int64_t n, int64_t F,
        const uint8_t* frags_blob, const int32_t* frag_lens,
        const int32_t* field_offs, const int32_t* field_lens,
        int64_t sf, int64_t si,
        const uint8_t* prefix, int64_t prefix_len, int32_t prefix_members,
        const uint8_t* ts_frag, int64_t ts_frag_len,
        int32_t ts_mode, int32_t ts_first,
        const uint8_t* suffix, int64_t suffix_len,
        uint8_t* out, int64_t out_cap) {
    if (F > 64) return -1;
    const uint8_t* cls = json_escape_class();
    int64_t frag_starts[64];
    int64_t acc = 0;
    int64_t frags_total = 0;
    for (int64_t f = 0; f < F; ++f) {
        frag_starts[f] = acc;
        acc += frag_lens[f];
        frags_total += frag_lens[f];
    }
    auto span_ok = [&](int64_t idx) -> bool {
        int32_t vlen = field_lens[idx];
        if (vlen < 0) return false;
        int32_t voff = field_offs[idx];
        return voff >= 0 && static_cast<int64_t>(voff) + vlen <= arena_len;
    };
    const uint8_t* out_end = out + out_cap;
    uint8_t* p = out;
    for (int64_t i = 0; i < n; ++i) {
        // conservative row bound: every value byte may expand 6x
        int64_t base = i * si;
        int64_t vbytes = 0;
        for (int64_t f = 0; f < F; ++f) {
            int64_t idx = base + f * sf;
            if (span_ok(idx)) vbytes += field_lens[idx];
        }
        int64_t bound = prefix_len + ts_frag_len + 48 + frags_total
                        + 4 * F + 6 * vbytes + suffix_len + 2;
        if (p + bound > out_end) return -1;
        memcpy(p, prefix, (size_t)prefix_len);
        p += prefix_len;
        bool members = prefix_members != 0;
        if (ts_mode != 0 && ts_first != 0) {
            if (members) { *p++ = ','; *p++ = ' '; }
            memcpy(p, ts_frag, (size_t)ts_frag_len);
            p += ts_frag_len;
            if (ts_mode == 2) {
                *p++ = '"'; p = put_iso8601(p, timestamps[i]); *p++ = '"';
            } else {
                p = put_decimal_i64(p, timestamps[i]);
            }
            members = true;
        }
        for (int64_t f = 0; f < F; ++f) {
            int64_t idx = base + f * sf;
            if (!span_ok(idx)) continue;
            if (members) { *p++ = ','; *p++ = ' '; }
            memcpy(p, frags_blob + frag_starts[f], (size_t)frag_lens[f]);
            p += frag_lens[f];
            p = put_json_escaped(p, arena + field_offs[idx],
                                 field_lens[idx], cls);
            *p++ = '"';
            members = true;
        }
        if (ts_mode != 0 && ts_first == 0) {
            if (members) { *p++ = ','; *p++ = ' '; }
            memcpy(p, ts_frag, (size_t)ts_frag_len);
            p += ts_frag_len;
            if (ts_mode == 2) {
                *p++ = '"'; p = put_iso8601(p, timestamps[i]); *p++ = '"';
            } else {
                p = put_decimal_i64(p, timestamps[i]);
            }
        }
        *p++ = '}';
        memcpy(p, suffix, (size_t)suffix_len);
        p += suffix_len;
    }
    return p - out;
}

// ---------------------------------------------------------------------------
// The file sink's write: append `len` bytes to the file at `path` (created
// 0666 less the umask if missing, as open(path, "ab") does) — open, write
// all of it, close.  One call, so one release of the interpreter lock for
// the three system calls.  Returns len, or -errno of the call that failed.
// ---------------------------------------------------------------------------
int64_t lct_append_file(const char* path, const uint8_t* data, int64_t len) {
    int fd;
    do {
        fd = open(path, O_WRONLY | O_APPEND | O_CREAT | O_CLOEXEC, 0666);
    } while (fd < 0 && errno == EINTR);
    if (fd < 0) return -static_cast<int64_t>(errno);
    int64_t done = 0;
    while (done < len) {
        ssize_t w = write(fd, data + done, static_cast<size_t>(len - done));
        if (w < 0) {
            if (errno == EINTR) continue;
            int64_t e = errno;
            close(fd);
            return -e;
        }
        done += w;
    }
    if (close(fd) != 0) return -static_cast<int64_t>(errno);
    return done;
}

// ---------------------------------------------------------------------------
// The file sink's flush of one columnar group in one call, so that the
// sink's sender thread lets go of the interpreter lock once per batch:
// an OR over the arena (a byte >= 0x80 anywhere sends the caller to the
// path that checks span by span: -2), lct_ndjson_serialize into `out`,
// lct_append_file of what it wrote.  phase_ns[0], [1]: nanoseconds spent
// assembling and writing (the spans flusher.serialize / flusher.write).
// Returns the bytes appended; -1 as lct_ndjson_serialize; -2 above;
// -(1000 + errno) when a system call of the write failed.
// ---------------------------------------------------------------------------
int64_t lct_ndjson_serialize_append(
        const char* path,
        const uint8_t* arena, int64_t arena_len, const int64_t* timestamps,
        int64_t n, int64_t F,
        const uint8_t* frags_blob, const int32_t* frag_lens,
        const int32_t* field_offs, const int32_t* field_lens,
        int64_t sf, int64_t si,
        const uint8_t* prefix, int64_t prefix_len, int32_t prefix_members,
        const uint8_t* ts_frag, int64_t ts_frag_len,
        int32_t ts_mode, int32_t ts_first,
        const uint8_t* suffix, int64_t suffix_len,
        uint8_t* out, int64_t out_cap, int64_t* phase_ns) {
    auto now_ns = []() -> int64_t {
        struct timespec ts;
        clock_gettime(CLOCK_MONOTONIC, &ts);
        return static_cast<int64_t>(ts.tv_sec) * 1000000000LL + ts.tv_nsec;
    };
    int64_t t0 = now_ns();
    uint8_t any = 0;
    for (int64_t i = 0; i < arena_len; ++i) any |= arena[i];
    if (any & 0x80) return -2;
    int64_t written = lct_ndjson_serialize(
        arena, arena_len, timestamps, n, F, frags_blob, frag_lens,
        field_offs, field_lens, sf, si, prefix, prefix_len, prefix_members,
        ts_frag, ts_frag_len, ts_mode, ts_first, suffix, suffix_len,
        out, out_cap);
    if (written < 0) return -1;
    int64_t t1 = now_ns();
    int64_t rc = lct_append_file(path, out, written);
    phase_ns[0] = t1 - t0;
    phase_ns[1] = now_ns() - t1;
    return rc < 0 ? rc - 1000 : written;
}

}  // extern "C"

extern "C" {

// ---------------------------------------------------------------------------
// CRC32C (Castagnoli) — required by Kafka record-batch v2 framing.
// Table-driven; table built on first use.
// ---------------------------------------------------------------------------
static uint32_t crc32c_table[256];
static bool crc32c_ready = false;

static void crc32c_init() {
    for (uint32_t i = 0; i < 256; ++i) {
        uint32_t crc = i;
        for (int j = 0; j < 8; ++j)
            crc = (crc >> 1) ^ (0x82F63B78u & (~(crc & 1) + 1));
        crc32c_table[i] = crc;
    }
    crc32c_ready = true;
}

}  // extern "C" — the fused-scan core below is a C++ template

// ---------------------------------------------------------------------------
// loongfuse: fused multi-accept DFA scan.
//
// One pass classifies a whole pattern set: `t256` is a byte-indexed
// transition table (class compression folded in at build time, so the
// serial dependency is a single L1-resident load per byte), `accept_tags`
// maps each state to the uint32 bitmask of patterns accepting in it.
// Rows are independent, so four advance in lockstep to hide the
// transition-load latency of each row's state chain (the PaREM-style
// parallel split, applied across rows instead of within one input).
// u8 state ids while S <= 256 (the whole table stays L1-resident for
// typical fused sets), u16 above.  Negative lengths scan as empty rows;
// out-of-arena spans classify as tag 0 rather than reading wild.
// ---------------------------------------------------------------------------

namespace {

template <typename StateT>
inline void dfa_scan_rows(const uint8_t* arena, int64_t arena_len,
                          const int64_t* offsets, const int32_t* lengths,
                          int64_t n, const StateT* t, int32_t start,
                          const uint32_t* accept_tags, uint32_t* tags_out) {
    int64_t i = 0;
    for (; i + 4 <= n; i += 4) {
        const uint8_t* r0 = arena + offsets[i];
        const uint8_t* r1 = arena + offsets[i + 1];
        const uint8_t* r2 = arena + offsets[i + 2];
        const uint8_t* r3 = arena + offsets[i + 3];
        int32_t l0 = lengths[i] < 0 ? 0 : lengths[i];
        int32_t l1 = lengths[i + 1] < 0 ? 0 : lengths[i + 1];
        int32_t l2 = lengths[i + 2] < 0 ? 0 : lengths[i + 2];
        int32_t l3 = lengths[i + 3] < 0 ? 0 : lengths[i + 3];
        bool in0 = offsets[i] >= 0 && offsets[i] + l0 <= arena_len;
        bool in1 = offsets[i + 1] >= 0 && offsets[i + 1] + l1 <= arena_len;
        bool in2 = offsets[i + 2] >= 0 && offsets[i + 2] + l2 <= arena_len;
        bool in3 = offsets[i + 3] >= 0 && offsets[i + 3] + l3 <= arena_len;
        if (!(in0 && in1 && in2 && in3)) {
            for (int64_t k = i; k < i + 4; ++k) {
                int32_t l = lengths[k] < 0 ? 0 : lengths[k];
                if (offsets[k] < 0 || offsets[k] + l > arena_len) {
                    tags_out[k] = 0;
                    continue;
                }
                const uint8_t* r = arena + offsets[k];
                uint32_t s = (uint32_t)start;
                for (int32_t p = 0; p < l; ++p)
                    s = t[(s << 8) | r[p]];
                tags_out[k] = accept_tags[s];
            }
            continue;
        }
        int32_t lmin = l0 < l1 ? l0 : l1;
        if (l2 < lmin) lmin = l2;
        if (l3 < lmin) lmin = l3;
        uint32_t s0 = (uint32_t)start, s1 = s0, s2 = s0, s3 = s0;
        for (int32_t p = 0; p < lmin; ++p) {
            s0 = t[(s0 << 8) | r0[p]];
            s1 = t[(s1 << 8) | r1[p]];
            s2 = t[(s2 << 8) | r2[p]];
            s3 = t[(s3 << 8) | r3[p]];
        }
        for (int32_t p = lmin; p < l0; ++p) s0 = t[(s0 << 8) | r0[p]];
        for (int32_t p = lmin; p < l1; ++p) s1 = t[(s1 << 8) | r1[p]];
        for (int32_t p = lmin; p < l2; ++p) s2 = t[(s2 << 8) | r2[p]];
        for (int32_t p = lmin; p < l3; ++p) s3 = t[(s3 << 8) | r3[p]];
        tags_out[i] = accept_tags[s0];
        tags_out[i + 1] = accept_tags[s1];
        tags_out[i + 2] = accept_tags[s2];
        tags_out[i + 3] = accept_tags[s3];
    }
    for (; i < n; ++i) {
        int32_t l = lengths[i] < 0 ? 0 : lengths[i];
        if (offsets[i] < 0 || offsets[i] + l > arena_len) {
            tags_out[i] = 0;
            continue;
        }
        const uint8_t* r = arena + offsets[i];
        uint32_t s = (uint32_t)start;
        for (int32_t p = 0; p < l; ++p) s = t[(s << 8) | r[p]];
        tags_out[i] = accept_tags[s];
    }
}

}  // namespace

extern "C" {

int64_t lct_dfa_scan(const uint8_t* arena, int64_t arena_len,
                     const int64_t* offsets, const int32_t* lengths,
                     int64_t n, const void* t256, int32_t n_states,
                     int32_t wide, int32_t start,
                     const uint32_t* accept_tags, uint32_t* tags_out) {
    if (n_states <= 0 || start < 0 || start >= n_states) return -1;
    if (wide) {
        if (n_states > 65536) return -1;
        dfa_scan_rows(arena, arena_len, offsets, lengths, n,
                      static_cast<const uint16_t*>(t256), start,
                      accept_tags, tags_out);
    } else {
        if (n_states > 256) return -1;
        dfa_scan_rows(arena, arena_len, offsets, lengths, n,
                      static_cast<const uint8_t*>(t256), start,
                      accept_tags, tags_out);
    }
    return 0;
}

uint32_t lct_crc32c(const uint8_t* data, int64_t len, uint32_t seed) {
    if (!crc32c_ready) crc32c_init();
    uint32_t crc = seed ^ 0xFFFFFFFFu;
    for (int64_t i = 0; i < len; ++i)
        crc = (crc >> 8) ^ crc32c_table[(crc ^ data[i]) & 0xFF];
    return crc ^ 0xFFFFFFFFu;
}

}  // extern "C"

extern "C" {

// ---------------------------------------------------------------------------
// Columnar JSON field extraction for flat-schema log events.
//
// For each event (a JSON object), extracts the values of F known keys as
// (offset, len) spans into the arena — zero copies:
//   * strings WITHOUT escapes  → span of the content between the quotes
//   * numbers / true/false/null → span of the raw token
//   * nested objects/arrays     → span of the raw JSON slice
// Events that don't fit the fast path (escaped strings, unknown keys,
// malformed JSON) get fallback_mask=1 and are handled by the host.
// out_offs/out_lens are [F * n] (field-major), len -1 = absent.
// ok[i]=1 iff the event parsed as an object on the fast path.
// ---------------------------------------------------------------------------

static inline int64_t jskip_ws(const uint8_t* a, int64_t p, int64_t end) {
    while (p < end && (a[p] == ' ' || a[p] == '\t' || a[p] == '\n' ||
                       a[p] == '\r'))
        ++p;
    return p;
}

// scan a string starting AFTER the opening quote; returns position of the
// closing quote or -1; sets *had_escape
static inline int64_t jscan_string(const uint8_t* a, int64_t p, int64_t end,
                                   bool* had_escape) {
    while (p < end) {
        uint8_t c = a[p];
        if (c == '\\') { *had_escape = true; p += 2; continue; }
        if (c == '"') return p;
        if (c < 0x20) { *had_escape = true; ++p; continue; }  // strict JSON:
        // raw control chars are invalid — flag so the event falls back to
        // the host parser, keeping both paths' accept/reject identical
        ++p;
    }
    return -1;
}

// strict JSON scalar token: number | true | false | null
static bool json_scalar_valid(const uint8_t* t, int64_t n) {
    if (n == 4 && memcmp(t, "true", 4) == 0) return true;
    if (n == 4 && memcmp(t, "null", 4) == 0) return true;
    if (n == 5 && memcmp(t, "false", 5) == 0) return true;
    int64_t i = 0;
    if (i < n && t[i] == '-') ++i;
    if (i >= n) return false;
    if (t[i] == '0') { ++i; }
    else if (t[i] >= '1' && t[i] <= '9') {
        while (i < n && t[i] >= '0' && t[i] <= '9') ++i;
    } else return false;
    if (i < n && t[i] == '.') {
        ++i;
        if (i >= n || t[i] < '0' || t[i] > '9') return false;
        while (i < n && t[i] >= '0' && t[i] <= '9') ++i;
    }
    if (i < n && (t[i] == 'e' || t[i] == 'E')) {
        ++i;
        if (i < n && (t[i] == '+' || t[i] == '-')) ++i;
        if (i >= n || t[i] < '0' || t[i] > '9') return false;
        while (i < n && t[i] >= '0' && t[i] <= '9') ++i;
    }
    return i == n;
}

void lct_json_extract(const uint8_t* arena, int64_t arena_len,
                      const int64_t* offsets, const int32_t* lengths,
                      int64_t n,
                      const uint8_t* keys_blob, const int32_t* key_lens,
                      int64_t F,
                      int32_t* out_offs, int32_t* out_lens,
                      uint8_t* ok, uint8_t* fallback_mask) {
    int64_t key_starts[128];
    if (F > 128) F = 128;
    {
        int64_t acc = 0;
        for (int64_t f = 0; f < F; ++f) { key_starts[f] = acc; acc += key_lens[f]; }
    }
    for (int64_t f = 0; f < F; ++f)
        for (int64_t i = 0; i < n; ++i) out_lens[f * n + i] = -1;

    for (int64_t i = 0; i < n; ++i) {
        ok[i] = 0;
        fallback_mask[i] = 0;
        int64_t p = offsets[i];
        int64_t end = p + lengths[i];
        if (p < 0 || end > arena_len) { fallback_mask[i] = 1; continue; }
        p = jskip_ws(arena, p, end);
        if (p >= end || arena[p] != '{') { fallback_mask[i] = 1; continue; }
        ++p;
        bool bad = false, fellback = false;
        p = jskip_ws(arena, p, end);
        if (p < end && arena[p] == '}') {
            // empty object: still only whitespace may follow
            int64_t q = jskip_ws(arena, p + 1, end);
            if (q == end) ok[i] = 1; else fallback_mask[i] = 1;
            continue;
        }
        while (p < end) {
            p = jskip_ws(arena, p, end);
            if (p >= end || arena[p] != '"') { bad = true; break; }
            bool kesc = false;
            int64_t kstart = p + 1;
            int64_t kq = jscan_string(arena, kstart, end, &kesc);
            if (kq < 0 || kesc) { fellback = true; break; }
            int64_t klen = kq - kstart;
            p = jskip_ws(arena, kq + 1, end);
            if (p >= end || arena[p] != ':') { bad = true; break; }
            p = jskip_ws(arena, p + 1, end);
            if (p >= end) { bad = true; break; }
            int64_t voff, vlen;
            uint8_t c = arena[p];
            if (c == '"') {
                bool vesc = false;
                int64_t vstart = p + 1;
                int64_t vq = jscan_string(arena, vstart, end, &vesc);
                if (vq < 0) { bad = true; break; }
                if (vesc) { fellback = true; break; }
                voff = vstart; vlen = vq - vstart;
                p = vq + 1;
            } else if (c == '{' || c == '[') {
                // bracket stack so mismatched nesting ({]}) is rejected
                uint8_t stack[64];
                int depth = 0;
                int64_t q = p;
                bool nested_bad = false;
                while (q < end) {
                    uint8_t d = arena[q];
                    if (d == '"') {
                        bool e2 = false;
                        int64_t sq = jscan_string(arena, q + 1, end, &e2);
                        if (sq < 0) { nested_bad = true; break; }
                        q = sq + 1;
                        continue;
                    }
                    if (d == '{' || d == '[') {
                        if (depth >= 64) { nested_bad = true; break; }
                        stack[depth++] = d;
                    } else if (d == '}' || d == ']') {
                        uint8_t want = (d == '}') ? '{' : '[';
                        if (depth == 0 || stack[depth - 1] != want) {
                            nested_bad = true;
                            break;
                        }
                        if (--depth == 0) { ++q; break; }
                    }
                    ++q;
                }
                if (nested_bad || depth != 0) { bad = true; break; }
                voff = p; vlen = q - p;
                p = q;
            } else {
                // number / true / false / null: scan then validate the token
                int64_t q = p;
                while (q < end && arena[q] != ',' && arena[q] != '}' &&
                       arena[q] != ' ' && arena[q] != '\t' &&
                       arena[q] != '\n' && arena[q] != '\r')
                    ++q;
                voff = p; vlen = q - p;
                if (vlen == 0 || !json_scalar_valid(arena + voff, vlen)) {
                    bad = true;
                    break;
                }
                p = q;
            }
            // match against known keys
            bool known = false;
            for (int64_t f = 0; f < F; ++f) {
                if (key_lens[f] == klen &&
                    memcmp(keys_blob + key_starts[f], arena + kstart,
                           static_cast<size_t>(klen)) == 0) {
                    out_offs[f * n + i] = static_cast<int32_t>(voff);
                    out_lens[f * n + i] = static_cast<int32_t>(vlen);
                    known = true;
                    break;
                }
            }
            if (!known) { fellback = true; break; }
            p = jskip_ws(arena, p, end);
            if (p < end && arena[p] == ',') { ++p; continue; }
            if (p < end && arena[p] == '}') {
                p = jskip_ws(arena, p + 1, end);
                if (p == end) ok[i] = 1;
                else bad = true;
                break;
            }
            bad = true;
            break;
        }
        if (fellback || bad) {
            fallback_mask[i] = 1;
            ok[i] = 0;
            for (int64_t f = 0; f < F; ++f) out_lens[f * n + i] = -1;
        }
    }
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Block codecs: LZ4 block + Snappy block, written to the PUBLIC formats
// (lz4 block spec; google/snappy format description). The reference links
// liblz4 (core/common/compression/Lz4Compressor.cpp) — this image has no
// lz4/snappy Python modules, and SLS's DEFAULT codec is LZ4
// (FlusherSLS.h:124-159) while Prometheus remote-write REQUIRES snappy,
// so the codecs live here behind ctypes.
// ---------------------------------------------------------------------------
extern "C" {

int64_t lct_lz4_bound(int64_t n) { return n + n / 255 + 16; }

int64_t lct_lz4_compress(const uint8_t* src, int64_t n,
                         uint8_t* dst, int64_t cap) {
    if (n < 0) return -1;
    if (n == 0) return 0;
    enum { HB = 16 };
    static thread_local uint32_t htab[1u << HB];
    memset(htab, 0, sizeof(htab));
    auto hash = [](uint32_t v) { return (v * 2654435761u) >> (32 - HB); };
    auto rd32 = [&](int64_t p) {
        uint32_t v; memcpy(&v, src + p, 4); return v;
    };
    int64_t ip = 0, anchor = 0, op = 0;
    const int64_t mflimit = n - 12;   // spec: no match may start after this
    const int64_t matchlimit = n - 5; // spec: last 5 bytes are literals
    while (ip < mflimit) {
        uint32_t h = hash(rd32(ip));
        int64_t ref = (int64_t)htab[h] - 1;
        htab[h] = (uint32_t)(ip + 1);
        if (ref < 0 || ip - ref > 65535 || rd32(ref) != rd32(ip)) {
            ip++;
            continue;
        }
        int64_t mlen = 4;
        while (ip + mlen < matchlimit && src[ref + mlen] == src[ip + mlen])
            mlen++;
        int64_t litlen = ip - anchor;
        if (op + litlen + litlen / 255 + mlen / 255 + 12 > cap) return -1;
        uint8_t* tok = dst + op++;
        if (litlen >= 15) {
            *tok = 0xF0;
            int64_t rest = litlen - 15;
            while (rest >= 255) { dst[op++] = 255; rest -= 255; }
            dst[op++] = (uint8_t)rest;
        } else {
            *tok = (uint8_t)(litlen << 4);
        }
        memcpy(dst + op, src + anchor, litlen);
        op += litlen;
        uint16_t off = (uint16_t)(ip - ref);
        dst[op++] = off & 0xFF;
        dst[op++] = off >> 8;
        int64_t mrem = mlen - 4;
        if (mrem >= 15) {
            *tok |= 0x0F;
            mrem -= 15;
            while (mrem >= 255) { dst[op++] = 255; mrem -= 255; }
            dst[op++] = (uint8_t)mrem;
        } else {
            *tok |= (uint8_t)mrem;
        }
        ip += mlen;
        anchor = ip;
    }
    int64_t litlen = n - anchor;
    if (op + litlen + litlen / 255 + 2 > cap) return -1;
    uint8_t* tok = dst + op++;
    if (litlen >= 15) {
        *tok = 0xF0;
        int64_t rest = litlen - 15;
        while (rest >= 255) { dst[op++] = 255; rest -= 255; }
        dst[op++] = (uint8_t)rest;
    } else {
        *tok = (uint8_t)(litlen << 4);
    }
    memcpy(dst + op, src + anchor, litlen);
    op += litlen;
    return op;
}

int64_t lct_lz4_decompress(const uint8_t* src, int64_t n,
                           uint8_t* dst, int64_t cap) {
    int64_t ip = 0, op = 0;
    while (ip < n) {
        uint8_t tok = src[ip++];
        int64_t litlen = tok >> 4;
        if (litlen == 15) {
            uint8_t b;
            do {
                if (ip >= n) return -1;
                b = src[ip++];
                litlen += b;
            } while (b == 255);
        }
        if (ip + litlen > n || op + litlen > cap) return -1;
        memcpy(dst + op, src + ip, litlen);
        ip += litlen;
        op += litlen;
        if (ip >= n) break;  // last sequence has no match
        if (ip + 2 > n) return -1;
        int64_t off = src[ip] | (src[ip + 1] << 8);
        ip += 2;
        if (off == 0 || off > op) return -1;
        int64_t mlen = (tok & 0x0F);
        if (mlen == 15) {
            uint8_t b;
            do {
                if (ip >= n) return -1;
                b = src[ip++];
                mlen += b;
            } while (b == 255);
        }
        mlen += 4;
        if (op + mlen > cap) return -1;
        // overlapping copy must run byte-wise
        for (int64_t i = 0; i < mlen; i++) dst[op + i] = dst[op + i - off];
        op += mlen;
    }
    return op;
}

int64_t lct_snappy_bound(int64_t n) { return 32 + n + n / 6; }

int64_t lct_snappy_compress(const uint8_t* src, int64_t n,
                            uint8_t* dst, int64_t cap) {
    if (n < 0) return -1;
    int64_t op = 0;
    // preamble: uncompressed length varint
    uint64_t v = (uint64_t)n;
    while (v >= 0x80) {
        if (op >= cap) return -1;
        dst[op++] = (uint8_t)(v | 0x80);
        v >>= 7;
    }
    if (op >= cap) return -1;
    dst[op++] = (uint8_t)v;
    auto emit_literal = [&](int64_t from, int64_t len) -> bool {
        while (len > 0) {
            int64_t take = len;
            if (op + take + 6 > cap) return false;
            if (take <= 60) {
                dst[op++] = (uint8_t)((take - 1) << 2);
            } else if (take - 1 <= 0xFF) {
                dst[op++] = 60 << 2;
                dst[op++] = (uint8_t)(take - 1);
            } else if (take - 1 <= 0xFFFF) {
                dst[op++] = 61 << 2;
                dst[op++] = (uint8_t)((take - 1) & 0xFF);
                dst[op++] = (uint8_t)((take - 1) >> 8);
            } else {
                take = 0x10000;  // chunk very long literals
                dst[op++] = 61 << 2;
                dst[op++] = 0xFF;
                dst[op++] = 0xFF;
            }
            memcpy(dst + op, src + from, take);
            op += take;
            from += take;
            len -= take;
        }
        return true;
    };
    enum { HB = 14 };
    static thread_local uint32_t htab[1u << HB];
    memset(htab, 0, sizeof(htab));
    auto hash = [](uint32_t x) { return (x * 0x1e35a7bd) >> (32 - HB); };
    auto rd32 = [&](int64_t p) {
        uint32_t x; memcpy(&x, src + p, 4); return x;
    };
    int64_t ip = 0, anchor = 0;
    while (ip + 4 <= n) {
        uint32_t h = hash(rd32(ip));
        int64_t ref = (int64_t)htab[h] - 1;
        htab[h] = (uint32_t)(ip + 1);
        if (ref < 0 || ip - ref > 65535 || rd32(ref) != rd32(ip)) {
            ip++;
            continue;
        }
        int64_t mlen = 4;
        while (ip + mlen < n && src[ref + mlen] == src[ip + mlen]) mlen++;
        if (!emit_literal(anchor, ip - anchor)) return -1;
        int64_t off = ip - ref;
        int64_t rem = mlen;
        while (rem > 0) {
            int64_t take = rem > 64 ? 64 : rem;
            if (take < 4) break;  // tail shorter than a copy: literal it
            if (op + 3 > cap) return -1;
            dst[op++] = (uint8_t)(((take - 1) << 2) | 2);  // 2-byte copy
            dst[op++] = (uint8_t)(off & 0xFF);
            dst[op++] = (uint8_t)(off >> 8);
            rem -= take;
        }
        ip += mlen - rem;
        if (rem > 0) {  // leftover (<4) emitted as literal with what follows
            anchor = ip;
            continue;
        }
        anchor = ip;
    }
    if (!emit_literal(anchor, n - anchor)) return -1;
    return op;
}

int64_t lct_snappy_uncompressed_len(const uint8_t* src, int64_t n) {
    uint64_t len = 0;
    int shift = 0;
    for (int64_t i = 0; i < n && i < 10; i++) {
        len |= (uint64_t)(src[i] & 0x7F) << shift;
        if (!(src[i] & 0x80)) return (int64_t)len;
        shift += 7;
    }
    return -1;
}

int64_t lct_snappy_decompress(const uint8_t* src, int64_t n,
                              uint8_t* dst, int64_t cap) {
    int64_t ip = 0;
    // skip preamble
    while (ip < n && (src[ip] & 0x80)) ip++;
    if (ip++ >= n) return -1;
    int64_t op = 0;
    while (ip < n) {
        uint8_t tag = src[ip++];
        uint8_t type = tag & 3;
        if (type == 0) {  // literal
            int64_t len = (tag >> 2) + 1;
            if (len > 60) {
                int extra = (int)len - 60;
                if (ip + extra > n) return -1;
                len = 0;
                for (int i = 0; i < extra; i++)
                    len |= (int64_t)src[ip + i] << (8 * i);
                len += 1;
                ip += extra;
            }
            if (ip + len > n || op + len > cap) return -1;
            memcpy(dst + op, src + ip, len);
            ip += len;
            op += len;
        } else {
            int64_t len, off;
            if (type == 1) {  // 1-byte offset copy
                if (ip >= n) return -1;
                len = ((tag >> 2) & 7) + 4;
                off = ((int64_t)(tag >> 5) << 8) | src[ip++];
            } else if (type == 2) {
                if (ip + 2 > n) return -1;
                len = (tag >> 2) + 1;
                off = src[ip] | ((int64_t)src[ip + 1] << 8);
                ip += 2;
            } else {
                if (ip + 4 > n) return -1;
                len = (tag >> 2) + 1;
                off = (int64_t)src[ip] | ((int64_t)src[ip + 1] << 8) |
                      ((int64_t)src[ip + 2] << 16) |
                      ((int64_t)src[ip + 3] << 24);
                ip += 4;
            }
            if (off == 0 || off > op || op + len > cap) return -1;
            for (int64_t i = 0; i < len; i++) dst[op + i] = dst[op + i - off];
            op += len;
        }
    }
    return op;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Tier-1 segment-program executor (host CPU tier).
//
// Executes the SAME compiled SegmentProgram IR the device kernels run
// (loongcollector_tpu/ops/regex/program.py), scalar per row, mirroring
// ops/kernels/field_extract.py op-for-op so the two paths are bit-identical
// (differentially fuzzed in tests/test_native_t1.py).  This is the
// CPU-degraded tier: when no accelerator is reachable the engine routes
// parse_batch here instead of the XLA:CPU emulation, matching how the
// reference's hot parse loop is native C++
// (core/plugin/processor/ProcessorParseRegexNative.cpp).
//
// Serialized program layout (int32 words; see ops/regex/native_exec.py):
//   [version=1, num_caps,
//    prefix_nwords, <prefix ops>,
//    has_pivot, {class_id, min, max(-1=INF), lazy}?,
//    suffix_nwords, <suffix ops, pre-reversed, literals forward-spelled>,
//    has_pivot2, {class_id, min, max, lazy}?,
//    mid_nwords, <mid ops>,
//    n_split, ids..., n_mid_end, ids...]
// Ops: 0 LIT lit_idx | 1 SPAN cls min max lazy | 2 FIXED cls n |
//      3 CAPSTART id | 4 CAPEND id | 5 OPT nwords body |
//      6 ALT nbranches (nwords body)*
// ---------------------------------------------------------------------------

namespace {

constexpr int kT1MaxCaps = 32;

struct T1State {
    int32_t cur;
    bool ok;
    int32_t cap_off[kT1MaxCaps];
    int32_t cap_len[kT1MaxCaps];
    int32_t cap_start[kT1MaxCaps];
};

// Per-class scan acceleration, derived from the membership table once per
// exec call: single-char negations ([^"]*, [^\]]+) scan via memchr/memrchr;
// classes whose members include every byte in [0x21,0xFF] (\S, \w-ish
// supersets) skip 8 bytes per SWAR word test; everything else runs a
// "truffle"-style SIMD membership scan (two pshufb nibble tables encode an
// arbitrary 256-byte set, 16 bytes per iteration) when the CPU has AVX2.
struct T1ClassInfo {
    int32_t neg_char;   // >=0: class == complement of exactly this byte
    bool hi_member;     // every byte in [0x21, 0xFF] is a member
    uint8_t tr_lo[16];  // truffle: bit (hi) of byte, indexed by lo nibble,
    uint8_t tr_hi[16];  //   for hi<8 (tr_lo) / hi>=8 (tr_hi)
};
constexpr int kT1MaxClasses = 64;

struct T1Ctx {
    const uint8_t* row;
    int32_t len;
    const uint8_t* classes;      // [K, 256] membership bytes
    const uint8_t* lit_blob;
    const int32_t* lit_offs;
    const int32_t* lit_lens;
    const T1ClassInfo* cinfo;
    int32_t ncaps;
    // Per-row stop-mask acceleration (linear programs only): for each
    // class used by SPAN/FIELD ops, a bitmask over the row marking
    // NON-member bytes (bits >= len forced set), built in one vector
    // sweep before the walk.  A field scan then collapses to a word
    // lookup + ctz instead of a fresh SIMD scan with its setup costs —
    // log rows average 5-15 short fields, so scan setup dominated the
    // per-row walk time.
    const int8_t* mask_slot;     // class id -> slot (or -1); null = off
    const uint64_t* mask_base;   // [nslots, mask_stride] bit words
    int32_t mask_words;          // words valid for THIS row
    int32_t mask_stride;
};

inline bool t1_member(const T1Ctx& c, int32_t cls, uint8_t b) {
    return c.classes[(int64_t)cls * 256 + b] != 0;
}

// Copy only the live capture slots (C of kT1MaxCaps): trial/backtrack state
// saves happen per OPT/ALT per row, and a full struct copy (~400 B) costs
// more than walking a typical log row.
inline void t1_copy(T1State& d, const T1State& s, int32_t C) {
    d.cur = s.cur;
    d.ok = s.ok;
    memcpy(d.cap_off, s.cap_off, (size_t)C * 4);
    memcpy(d.cap_len, s.cap_len, (size_t)C * 4);
    memcpy(d.cap_start, s.cap_start, (size_t)C * 4);
}

inline uint64_t t1_load8(const uint8_t* p) {
    uint64_t x;
    memcpy(&x, p, 8);
    return x;
}

// SWAR: flags (high bit per lane) for bytes < 0x21
inline uint64_t t1_low_bytes(uint64_t x) {
    return (x - 0x2121212121212121ULL) & ~x & 0x8080808080808080ULL;
}

#if defined(__x86_64__)
static const bool g_has_avx2 = __builtin_cpu_supports("avx2");

// Truffle block: returns a bitmask of NON-member bytes among the 16 loaded.
__attribute__((target("avx2"))) inline uint32_t t1_truffle16(
        const uint8_t* p, __m128i lo_tbl, __m128i hi_tbl) {
    const __m128i highconst = _mm_set1_epi8((char)0x80);
    const __m128i bits = _mm_set1_epi64x(0x8040201008040201LL);
    __m128i v = _mm_loadu_si128((const __m128i*)p);
    __m128i shuf1 = _mm_shuffle_epi8(lo_tbl, v);
    __m128i shuf2 = _mm_shuffle_epi8(hi_tbl, _mm_xor_si128(v, highconst));
    __m128i nib_hi = _mm_andnot_si128(highconst, _mm_srli_epi64(v, 4));
    __m128i shuf3 = _mm_shuffle_epi8(bits, nib_hi);
    __m128i t = _mm_and_si128(_mm_or_si128(shuf1, shuf2), shuf3);
    __m128i nonmem = _mm_cmpeq_epi8(t, _mm_setzero_si128());
    return (uint32_t)_mm_movemask_epi8(nonmem);
}

// Forward member run via truffle; falls back to the table near the tail.
__attribute__((target("avx2"))) int32_t t1_truffle_scan_fwd(
        const uint8_t* row, int32_t len, int32_t start,
        const T1ClassInfo& ci, const uint8_t* tbl) {
    __m128i lo = _mm_loadu_si128((const __m128i*)ci.tr_lo);
    __m128i hi = _mm_loadu_si128((const __m128i*)ci.tr_hi);
    int32_t i = start;
    for (; i + 16 <= len; i += 16) {
        uint32_t nm = t1_truffle16(row + i, lo, hi);
        if (nm) return i + (int32_t)__builtin_ctz(nm);
    }
    while (i < len && tbl[row[i]]) ++i;
    return i;
}

// Backward member run via truffle (run ends at cur, exclusive).
__attribute__((target("avx2"))) int32_t t1_truffle_scan_rev(
        const uint8_t* row, int32_t cur, const T1ClassInfo& ci,
        const uint8_t* tbl) {
    __m128i lo = _mm_loadu_si128((const __m128i*)ci.tr_lo);
    __m128i hi = _mm_loadu_si128((const __m128i*)ci.tr_hi);
    int32_t i = cur;
    for (; i >= 16; i -= 16) {
        uint32_t nm = t1_truffle16(row + i - 16, lo, hi);
        if (nm) return i - 16 + (32 - (int32_t)__builtin_clz(nm));
    }
    while (i > 0 && tbl[row[i - 1]]) --i;
    return i;
}
#else
static const bool g_has_avx2 = false;
inline int32_t t1_truffle_scan_fwd(const uint8_t*, int32_t, int32_t,
                                   const T1ClassInfo&, const uint8_t*) {
    return -1;
}
inline int32_t t1_truffle_scan_rev(const uint8_t*, int32_t,
                                   const T1ClassInfo&, const uint8_t*) {
    return -1;
}
#endif

// ---------------------------------------------------------------------------
// Stop-mask builders: one vector sweep over the row produces, per class, a
// bitmask of non-member positions (bits >= len forced set so scans stop at
// the row end).  `avail` is the addressable bytes from row start (to the
// arena end) — full 32-byte loads run while i+32 <= avail; only the arena's
// final tail falls back to scalar.

constexpr int32_t kT1MaskSlots = 8;

// Everything the per-row mask sweep needs, resolved once per exec call.
// Every class — including single-char negations — runs the same truffle
// sweep (uniformity keeps the per-slot state in registers).
struct T1MaskPlan {
    int32_t n_slots;
    const T1ClassInfo* ci[kT1MaskSlots];  // truffle nibble tables
    const uint8_t* tbl[kT1MaskSlots];     // scalar-tail membership table
};

#if defined(__x86_64__)
// One sweep, all classes: each 32-byte block is loaded ONCE and evaluated
// against every slot.  The slot count is a template parameter so the
// per-slot vectors live in ymm registers and the inner loops fully unroll;
// every class (including single-char negations) runs the uniform truffle
// path — the nibble-decompose work (nib_hi/shuf3/vx) is shared across all
// slots, so an extra class costs ~6 ops per block.
template <int NS>
__attribute__((target("avx2"))) static void t1_mask_sweepT(
        const uint8_t* row, int32_t len, int64_t avail,
        const T1MaskPlan& plan, uint64_t* maskbuf, int32_t stride,
        int32_t n_words) {
    const __m256i highconst = _mm256_set1_epi8((char)0x80);
    const __m256i bits_tbl = _mm256_set1_epi64x(0x8040201008040201LL);
    __m256i lo[NS], hi[NS];
    for (int32_t s = 0; s < NS; ++s) {
        lo[s] = _mm256_broadcastsi128_si256(
            _mm_loadu_si128((const __m128i*)plan.ci[s]->tr_lo));
        hi[s] = _mm256_broadcastsi128_si256(
            _mm_loadu_si128((const __m128i*)plan.ci[s]->tr_hi));
    }
    int32_t i = 0;
    for (int32_t w = 0; w < n_words; ++w) {
        for (int32_t half = 0; half < 2; ++half, i += 32) {
            uint32_t m[NS];
            if (i >= len) {
                // wholly past the row: seal() will set these bits
                for (int32_t s = 0; s < NS; ++s) m[s] = 0;
            } else if (i + 32 <= avail) {
                __m256i v = _mm256_loadu_si256((const __m256i*)(row + i));
                __m256i nib_hi = _mm256_andnot_si256(
                    highconst, _mm256_srli_epi64(v, 4));
                __m256i shuf3 = _mm256_shuffle_epi8(bits_tbl, nib_hi);
                __m256i vx = _mm256_xor_si256(v, highconst);
                for (int32_t s = 0; s < NS; ++s) {
                    __m256i t = _mm256_and_si256(
                        _mm256_or_si256(_mm256_shuffle_epi8(lo[s], v),
                                        _mm256_shuffle_epi8(hi[s], vx)),
                        shuf3);
                    m[s] = (uint32_t)_mm256_movemask_epi8(
                        _mm256_cmpeq_epi8(t, _mm256_setzero_si256()));
                }
            } else {
                for (int32_t s = 0; s < NS; ++s) {
                    uint32_t acc = 0;
                    const uint8_t* tbl = plan.tbl[s];
                    for (int32_t j = 0; j < 32 && i + j < len; ++j)
                        if (!tbl[row[i + j]]) acc |= 1u << j;
                    m[s] = acc;
                }
            }
            for (int32_t s = 0; s < NS; ++s) {
                uint64_t* out = maskbuf + (int64_t)s * stride;
                if (half == 0)
                    out[w] = m[s];
                else
                    out[w] |= (uint64_t)m[s] << 32;
            }
        }
    }
}

// AVX-512BW sweep: one 64-byte block per mask word, mask-register loads
// suppress faults on the tail so there is no scalar path at all, and
// testn_epi8_mask yields the 64-bit non-member word directly.
static const bool g_has_avx512 = __builtin_cpu_supports("avx512bw");

template <int NS>
__attribute__((target("avx512f,avx512bw"))) static void t1_mask_sweep512T(
        const uint8_t* row, int32_t len, const T1MaskPlan& plan,
        uint64_t* maskbuf, int32_t stride, int32_t n_words) {
    const __m512i highconst = _mm512_set1_epi8((char)0x80);
    const __m512i bits_tbl = _mm512_set1_epi64(0x8040201008040201LL);
    __m512i lo[NS], hi[NS];
    for (int32_t s = 0; s < NS; ++s) {
        lo[s] = _mm512_broadcast_i32x4(
            _mm_loadu_si128((const __m128i*)plan.ci[s]->tr_lo));
        hi[s] = _mm512_broadcast_i32x4(
            _mm_loadu_si128((const __m128i*)plan.ci[s]->tr_hi));
    }
    for (int32_t w = 0; w < n_words; ++w) {
        int32_t i = w << 6;
        int32_t rem = len - i;
        __mmask64 loadm = rem >= 64 ? ~0ULL
                          : rem <= 0 ? 0 : ((1ULL << rem) - 1ULL);
        __m512i v = _mm512_maskz_loadu_epi8(loadm, row + i);
        __m512i nib_hi =
            _mm512_andnot_si512(highconst, _mm512_srli_epi64(v, 4));
        __m512i shuf3 = _mm512_shuffle_epi8(bits_tbl, nib_hi);
        __m512i vx = _mm512_xor_si512(v, highconst);
        for (int32_t s = 0; s < NS; ++s) {
            __m512i t = _mm512_and_si512(
                _mm512_or_si512(_mm512_shuffle_epi8(lo[s], v),
                                _mm512_shuffle_epi8(hi[s], vx)),
                shuf3);
            maskbuf[(int64_t)s * stride + w] =
                (uint64_t)_mm512_testn_epi8_mask(t, t);
        }
    }
}

static void t1_mask_build_all512(const uint8_t* row, int32_t len,
                                 const T1MaskPlan& plan, uint64_t* maskbuf,
                                 int32_t stride, int32_t n_words) {
    switch (plan.n_slots) {
    case 1: t1_mask_sweep512T<1>(row, len, plan, maskbuf, stride, n_words); break;
    case 2: t1_mask_sweep512T<2>(row, len, plan, maskbuf, stride, n_words); break;
    case 3: t1_mask_sweep512T<3>(row, len, plan, maskbuf, stride, n_words); break;
    case 4: t1_mask_sweep512T<4>(row, len, plan, maskbuf, stride, n_words); break;
    case 5: t1_mask_sweep512T<5>(row, len, plan, maskbuf, stride, n_words); break;
    case 6: t1_mask_sweep512T<6>(row, len, plan, maskbuf, stride, n_words); break;
    case 7: t1_mask_sweep512T<7>(row, len, plan, maskbuf, stride, n_words); break;
    default: t1_mask_sweep512T<8>(row, len, plan, maskbuf, stride, n_words); break;
    }
}

static void t1_mask_build_all(const uint8_t* row, int32_t len,
                              int64_t avail, const T1MaskPlan& plan,
                              uint64_t* maskbuf, int32_t stride,
                              int32_t n_words) {
    if (g_has_avx512) {
        t1_mask_build_all512(row, len, plan, maskbuf, stride, n_words);
        return;
    }
    switch (plan.n_slots) {
    case 1: t1_mask_sweepT<1>(row, len, avail, plan, maskbuf, stride, n_words); break;
    case 2: t1_mask_sweepT<2>(row, len, avail, plan, maskbuf, stride, n_words); break;
    case 3: t1_mask_sweepT<3>(row, len, avail, plan, maskbuf, stride, n_words); break;
    case 4: t1_mask_sweepT<4>(row, len, avail, plan, maskbuf, stride, n_words); break;
    case 5: t1_mask_sweepT<5>(row, len, avail, plan, maskbuf, stride, n_words); break;
    case 6: t1_mask_sweepT<6>(row, len, avail, plan, maskbuf, stride, n_words); break;
    case 7: t1_mask_sweepT<7>(row, len, avail, plan, maskbuf, stride, n_words); break;
    default: t1_mask_sweepT<8>(row, len, avail, plan, maskbuf, stride, n_words); break;
    }
}
#else
static void t1_mask_build_all(const uint8_t*, int32_t, int64_t,
                              const T1MaskPlan&, uint64_t*, int32_t,
                              int32_t) {}
#endif

// Force every bit at position >= len set (scan stops at row end).
static inline void t1_mask_seal(uint64_t* out, int32_t n_words,
                                int32_t len) {
    int32_t w = len >> 6;
    if (w < n_words) {
        int32_t b = len & 63;
        out[w] |= ~((b ? (1ull << b) : 1ull) - 1ull);
        for (int32_t k = w + 1; k < n_words; ++k) out[k] = ~0ull;
    }
}

// First stop (non-member) position >= start, from the precomputed mask.
static inline int32_t t1_mask_find(const uint64_t* m, int32_t n_words,
                                   int32_t start) {
    int32_t w = start >> 6;
    if (w >= n_words) return n_words << 6;  // defensive: never read past
    uint64_t bits = m[w] >> (start & 63);
    if (bits) return start + (int32_t)__builtin_ctzll(bits);
    for (++w; w < n_words; ++w)
        if (m[w]) return (w << 6) + (int32_t)__builtin_ctzll(m[w]);
    return n_words << 6;  // unreachable: seal() guarantees a set bit
}

// Maximal forward run of class members starting at `start`.
inline int32_t t1_scan_fwd(const T1Ctx& c, int32_t cls, int32_t start) {
    if (c.mask_base != nullptr) {
        int8_t s = c.mask_slot[cls];
        if (s >= 0)
            return t1_mask_find(c.mask_base + (int64_t)s * c.mask_stride,
                                c.mask_words, start);
    }
    const T1ClassInfo& ci = c.cinfo[cls];
    if (ci.neg_char >= 0) {
        const void* hit = memchr(c.row + start, ci.neg_char, c.len - start);
        return hit ? (int32_t)((const uint8_t*)hit - c.row) : c.len;
    }
    const uint8_t* tbl = c.classes + (int64_t)cls * 256;
    if (g_has_avx2)
        return t1_truffle_scan_fwd(c.row, c.len, start, ci, tbl);
    int32_t end = start;
    if (ci.hi_member) {
        while (end + 8 <= c.len && !t1_low_bytes(t1_load8(c.row + end)))
            end += 8;
    }
    while (end < c.len && tbl[c.row[end]]) ++end;
    return end;
}

// Maximal backward run of class members ending at `cur` (exclusive).
inline int32_t t1_scan_rev(const T1Ctx& c, int32_t cls, int32_t cur) {
    const T1ClassInfo& ci = c.cinfo[cls];
    if (ci.neg_char >= 0) {
#ifdef _GNU_SOURCE
        const void* hit = memrchr(c.row, ci.neg_char, cur);
        return hit ? (int32_t)((const uint8_t*)hit - c.row) + 1 : 0;
#endif
    }
    const uint8_t* tbl = c.classes + (int64_t)cls * 256;
    if (g_has_avx2)
        return t1_truffle_scan_rev(c.row, cur, ci, tbl);
    int32_t start = cur;
    if (ci.hi_member) {
        while (start >= 8 && !t1_low_bytes(t1_load8(c.row + start - 8)))
            start -= 8;
    }
    while (start > 0 && tbl[c.row[start - 1]]) --start;
    return start;
}

// Forward walk (field_extract.py emit()): on failure sets st.ok=false and
// returns immediately — later ops only touch state that a failed trial
// discards, so the shortcut is semantics-preserving.
void t1_emit(const T1Ctx& c, const int32_t* w, int64_t nw, T1State& st) {
    int64_t i = 0;
    while (i < nw) {
        switch (w[i]) {
        case 0: {  // LIT (1–2 byte literals inline: memcmp call costs more)
            int32_t li = w[i + 1];
            int32_t k = c.lit_lens[li];
            const uint8_t* lp = c.lit_blob + c.lit_offs[li];
            const uint8_t* rp = c.row + st.cur;
            if (st.cur + k > c.len ||
                (k == 1 ? rp[0] != lp[0]
                 : k == 2 ? (rp[0] != lp[0] || rp[1] != lp[1])
                          : memcmp(rp, lp, k) != 0)) {
                st.ok = false;
                return;
            }
            st.cur += k;
            i += 2;
            break;
        }
        case 1: {  // SPAN: maximal munch (compiler proved follow-disjoint)
            int32_t cls = w[i + 1], mn = w[i + 2], mx = w[i + 3];
            int32_t end = t1_scan_fwd(c, cls, st.cur);
            int32_t run = end - st.cur;
            if (run < mn || (mx >= 0 && run > mx)) {
                st.ok = false;
                return;
            }
            st.cur = end;
            i += 5;
            break;
        }
        case 2: {  // FIXED
            int32_t cls = w[i + 1], n = w[i + 2];
            if (st.cur + n > c.len) {
                st.ok = false;
                return;
            }
            for (int32_t j = 0; j < n; ++j)
                if (!t1_member(c, cls, c.row[st.cur + j])) {
                    st.ok = false;
                    return;
                }
            st.cur += n;
            i += 3;
            break;
        }
        case 3:
            st.cap_start[w[i + 1]] = st.cur;
            i += 2;
            break;
        case 4: {
            int32_t id = w[i + 1];
            st.cap_off[id] = st.cap_start[id];
            st.cap_len[id] = st.cur - st.cap_start[id];
            i += 2;
            break;
        }
        case 5: {  // OPT: greedy preference — keep body iff it matched
            int32_t bw = w[i + 1];
            if (bw < 0 || i + 2 + bw > nw) {
                st.ok = false;
                return;
            }
            T1State save;
            t1_copy(save, st, c.ncaps);
            t1_emit(c, w + i + 2, bw, st);
            if (!st.ok) t1_copy(st, save, c.ncaps);
            i += 2 + bw;
            break;
        }
        case 6: {  // ALT: first branch whose whole body matches
            int32_t nb = w[i + 1];
            T1State before;
            t1_copy(before, st, c.ncaps);
            int64_t j = i + 2;
            bool chosen = false;
            for (int32_t b = 0; b < nb; ++b) {
                if (j >= nw) {
                    st.ok = false;
                    return;
                }
                int32_t bw = w[j];
                if (bw < 0 || j + 1 + bw > nw) {
                    st.ok = false;
                    return;
                }
                if (!chosen) {
                    T1State trial;
                    t1_copy(trial, before, c.ncaps);
                    t1_emit(c, w + j + 1, bw, trial);
                    if (trial.ok) {
                        t1_copy(st, trial, c.ncaps);
                        chosen = true;
                    }
                }
                j += 1 + bw;
            }
            i = j;
            if (!chosen) {
                t1_copy(st, before, c.ncaps);
                st.ok = false;
                return;
            }
            break;
        }
        default:
            st.ok = false;
            return;
        }
    }
}

// Reverse walk (field_extract.py emit_reverse()): cur is the EXCLUSIVE end
// boundary moving toward 0; ops arrive pre-reversed with literals stored in
// forward spelling; CAPEND records the right edge, CAPSTART closes.
void t1_emit_rev(const T1Ctx& c, const int32_t* w, int64_t nw, T1State& st,
                 int32_t floor_) {
    int64_t i = 0;
    while (i < nw) {
        switch (w[i]) {
        case 0: {  // LIT ending at cur
            int32_t li = w[i + 1];
            int32_t k = c.lit_lens[li];
            int32_t start = st.cur - k;
            const uint8_t* lp = c.lit_blob + c.lit_offs[li];
            const uint8_t* rp = c.row + start;
            if (start < 0 ||
                (k == 1 ? rp[0] != lp[0]
                 : k == 2 ? (rp[0] != lp[0] || rp[1] != lp[1])
                          : memcmp(rp, lp, k) != 0)) {
                st.ok = false;
                return;
            }
            st.cur = start;
            i += 2;
            break;
        }
        case 1: {  // SPAN: maximal run ending at cur, clamped by max/floor
            int32_t cls = w[i + 1], mn = w[i + 2], mx = w[i + 3];
            int32_t start = t1_scan_rev(c, cls, st.cur);
            if (mx >= 0 && start < st.cur - mx) start = st.cur - mx;
            if (start < floor_) start = floor_;
            if (start < 0) start = 0;
            if (start > st.cur) start = st.cur;
            if (st.cur - start < mn) {
                st.ok = false;
                return;
            }
            st.cur = start;
            i += 5;
            break;
        }
        case 2: {  // FIXED backward
            int32_t cls = w[i + 1], n = w[i + 2];
            int32_t start = st.cur - n;
            if (start < 0) {
                st.ok = false;
                return;
            }
            for (int32_t j = start; j < st.cur; ++j)
                if (!t1_member(c, cls, c.row[j])) {
                    st.ok = false;
                    return;
                }
            st.cur = start;
            i += 3;
            break;
        }
        case 3: {  // CAPSTART closes the group (left edge)
            int32_t id = w[i + 1];
            st.cap_off[id] = st.cur;
            st.cap_len[id] = st.cap_start[id] - st.cur;
            i += 2;
            break;
        }
        case 4:  // CAPEND records the right edge
            st.cap_start[w[i + 1]] = st.cur;
            i += 2;
            break;
        case 5: {
            int32_t bw = w[i + 1];
            if (bw < 0 || i + 2 + bw > nw) {
                st.ok = false;
                return;
            }
            T1State save;
            t1_copy(save, st, c.ncaps);
            t1_emit_rev(c, w + i + 2, bw, st, floor_);
            if (!st.ok) t1_copy(st, save, c.ncaps);
            i += 2 + bw;
            break;
        }
        case 6: {
            int32_t nb = w[i + 1];
            T1State before;
            t1_copy(before, st, c.ncaps);
            int64_t j = i + 2;
            bool chosen = false;
            for (int32_t b = 0; b < nb; ++b) {
                if (j >= nw) {
                    st.ok = false;
                    return;
                }
                int32_t bw = w[j];
                if (bw < 0 || j + 1 + bw > nw) {
                    st.ok = false;
                    return;
                }
                if (!chosen) {
                    T1State trial;
                    t1_copy(trial, before, c.ncaps);
                    t1_emit_rev(c, w + j + 1, bw, trial, floor_);
                    if (trial.ok) {
                        t1_copy(st, trial, c.ncaps);
                        chosen = true;
                    }
                }
                j += 1 + bw;
            }
            i = j;
            if (!chosen) {
                t1_copy(st, before, c.ncaps);
                st.ok = false;
                return;
            }
            break;
        }
        default:
            st.ok = false;
            return;
        }
    }
}

struct T1Header {
    int32_t num_caps;
    const int32_t* prefix;
    int64_t prefix_n;
    bool has_pivot;
    int32_t p1_cls, p1_min, p1_max, p1_lazy;
    const int32_t* suffix;
    int64_t suffix_n;
    bool has_pivot2;
    int32_t p2_cls, p2_min, p2_max;
    const int32_t* mid;
    int64_t mid_n;
    int32_t mid_fixed;       // length of the boundary literal in mid ops
    int32_t mid_lit_idx;     // literal index of the boundary literal
    const int32_t* split_ids;
    int32_t n_split;
    const int32_t* mid_end_ids;
    int32_t n_mid_end;
};

// Recursive op-stream validation: every class id / literal index in range,
// tags known, nested body lengths within the section.
bool t1_validate_ops(const int32_t* w, int64_t nw, int64_t n_classes,
                     int64_t n_lits, int32_t num_caps) {
    int64_t i = 0;
    while (i < nw) {
        switch (w[i]) {
        case 0:
            if (i + 2 > nw || w[i + 1] < 0 || w[i + 1] >= n_lits)
                return false;
            i += 2;
            break;
        case 1:
            if (i + 5 > nw || w[i + 1] < 0 || w[i + 1] >= n_classes)
                return false;
            i += 5;
            break;
        case 2:
            if (i + 3 > nw || w[i + 1] < 0 || w[i + 1] >= n_classes ||
                w[i + 2] < 0)
                return false;
            i += 3;
            break;
        case 3:
        case 4:
            if (i + 2 > nw || w[i + 1] < 0 || w[i + 1] >= num_caps)
                return false;
            i += 2;
            break;
        case 5: {
            if (i + 2 > nw) return false;
            int32_t bw = w[i + 1];
            if (bw < 0 || i + 2 + bw > nw ||
                !t1_validate_ops(w + i + 2, bw, n_classes, n_lits, num_caps))
                return false;
            i += 2 + bw;
            break;
        }
        case 6: {
            if (i + 2 > nw) return false;
            int32_t nb = w[i + 1];
            if (nb < 0) return false;
            int64_t j = i + 2;
            for (int32_t b = 0; b < nb; ++b) {
                if (j >= nw) return false;
                int32_t bw = w[j];
                if (bw < 0 || j + 1 + bw > nw ||
                    !t1_validate_ops(w + j + 1, bw, n_classes, n_lits,
                                     num_caps))
                    return false;
                j += 1 + bw;
            }
            i = j;
            break;
        }
        default:
            return false;
        }
    }
    return true;
}

bool t1_parse_header(const int32_t* w, int64_t nw, int64_t n_classes,
                     const int32_t* lit_lens, int64_t n_lits, T1Header& h) {
    int64_t i = 0;
    if (nw < 3 || w[i++] != 1) return false;
    h.num_caps = w[i++];
    if (h.num_caps < 1 || h.num_caps > kT1MaxCaps) return false;
    h.prefix_n = w[i++];
    if (h.prefix_n < 0 || i + h.prefix_n > nw) return false;
    h.prefix = w + i;
    i += h.prefix_n;
    if (i >= nw) return false;
    h.has_pivot = w[i++] != 0;
    if (h.has_pivot) {
        if (i + 4 > nw) return false;
        h.p1_cls = w[i];
        h.p1_min = w[i + 1];
        h.p1_max = w[i + 2];
        h.p1_lazy = w[i + 3];
        i += 4;
    }
    if (i >= nw) return false;
    h.suffix_n = w[i++];
    if (h.suffix_n < 0 || i + h.suffix_n > nw) return false;
    h.suffix = w + i;
    i += h.suffix_n;
    if (i >= nw) return false;
    h.has_pivot2 = w[i++] != 0;
    if (h.has_pivot2) {
        if (i + 4 > nw) return false;
        h.p2_cls = w[i];
        h.p2_min = w[i + 1];
        h.p2_max = w[i + 2];
        i += 4;
    }
    if (i >= nw) return false;
    h.mid_n = w[i++];
    if (h.mid_n < 0 || i + h.mid_n > nw) return false;
    h.mid = w + i;
    i += h.mid_n;
    h.mid_fixed = 0;
    h.mid_lit_idx = -1;
    for (int64_t j = 0; j < h.mid_n;) {  // locate the boundary literal
        switch (h.mid[j]) {
        case 0:
            h.mid_lit_idx = h.mid[j + 1];
            if (h.mid_lit_idx < 0 || h.mid_lit_idx >= n_lits) return false;
            h.mid_fixed = lit_lens[h.mid_lit_idx];
            j += 2;
            break;
        case 3:
        case 4:
            j += 2;
            break;
        default:
            return false;  // mid ops are one Lit + cap markers only
        }
        if (h.mid_lit_idx >= 0) break;
    }
    if (i >= nw) return false;
    h.n_split = w[i++];
    if (h.n_split < 0 || i + h.n_split > nw) return false;
    h.split_ids = w + i;
    i += h.n_split;
    if (i >= nw) return false;
    h.n_mid_end = w[i++];
    if (h.n_mid_end < 0 || i + h.n_mid_end > nw) return false;
    h.mid_end_ids = w + i;
    i += h.n_mid_end;
    if (h.has_pivot2 && (!h.has_pivot || h.mid_lit_idx < 0)) return false;
    if (h.has_pivot && (h.p1_cls < 0 || h.p1_cls >= n_classes)) return false;
    if (h.has_pivot2 && (h.p2_cls < 0 || h.p2_cls >= n_classes)) return false;
    if (!t1_validate_ops(h.prefix, h.prefix_n, n_classes, n_lits,
                         h.num_caps) ||
        !t1_validate_ops(h.suffix, h.suffix_n, n_classes, n_lits,
                         h.num_caps) ||
        !t1_validate_ops(h.mid, h.mid_n, n_classes, n_lits, h.num_caps))
        return false;
    for (int32_t k = 0; k < h.n_split; ++k)
        if (h.split_ids[k] < 0 || h.split_ids[k] >= h.num_caps) return false;
    for (int32_t k = 0; k < h.n_mid_end; ++k)
        if (h.mid_end_ids[k] < 0 || h.mid_end_ids[k] >= h.num_caps)
            return false;
    return i == nw;
}

inline bool t1_all_member(const T1Ctx& c, int32_t cls, int32_t lo,
                          int32_t hi) {
    if (hi <= lo) return true;
    const T1ClassInfo& ci = c.cinfo[cls];
    if (ci.neg_char >= 0)
        return memchr(c.row + lo, ci.neg_char, hi - lo) == nullptr;
    return t1_scan_fwd(c, cls, lo) >= hi;
}

// ---------------------------------------------------------------------------
// Decoded-op fast interpreter for the forward prefix walk.  The dominant
// motif in compiled segment programs is CapStart→Span→CapEnd→Lit (a captured
// field followed by its delimiter); decoding the word stream once per batch
// and fusing that motif into a single FIELD op removes most per-row dispatch.
// When the span class is a single-char negation whose terminator IS the
// literal's first byte ( ([^\]]+)\] , ([^"]*)" ), one memchr finds the span
// end and the delimiter together.  OPT/ALT bodies decode inline after their
// parent op; capture-free shapes are further specialized (kinds 8/10/11) to
// copy-free trials, the rest keep the generic save/restore trials.
// ---------------------------------------------------------------------------
struct T1DecOp {
    int32_t kind;         // 0..6 = word op kinds; 7 = FIELD
    int32_t a, b, c2, d;  // kind-specific (FIELD: cap_id, cls, min, max)
    int32_t lit;          // FIELD: trailing literal index (-1 = none)
    const int32_t* w;     // kind 8 (all-literal ALT): raw branch words
    int32_t wn;           //   width in words
    const uint64_t* mask; // SPAN/FIELD: resolved per-class stop-mask slot
                          // (filled by the exec that owns the mask buffer;
                          // null = use the classic scanners)
};

constexpr int kT1MaxDecOps = 192;

inline bool t1_lit_at(const T1Ctx& c, int32_t li, int32_t pos) {
    int32_t k = c.lit_lens[li];
    if (pos + k > c.len) return false;
    const uint8_t* lp = c.lit_blob + c.lit_offs[li];
    const uint8_t* rp = c.row + pos;
    // literals ≤ 8 bytes compare as fixed-width loads (a memcmp CALL per
    // trial dominates literal-alternation walks: 12 month branches × call
    // overhead beats the actual byte compares by an order of magnitude)
    switch (k) {
    case 1: return rp[0] == lp[0];
    case 2: return rp[0] == lp[0] && rp[1] == lp[1];
    case 3: return rp[0] == lp[0] && rp[1] == lp[1] && rp[2] == lp[2];
    case 4: {
        uint32_t a, b;
        memcpy(&a, rp, 4); memcpy(&b, lp, 4);
        return a == b;
    }
    default:
        if (k <= 8) {
            uint64_t a = 0, b = 0;
            memcpy(&a, rp, 4); memcpy(&b, lp, 4);
            uint64_t a2 = 0, b2 = 0;
            memcpy(&a2, rp + k - 4, 4); memcpy(&b2, lp + k - 4, 4);
            return a == b && a2 == b2;
        }
        return memcmp(rp, lp, k) == 0;
    }
}

// Decode + fuse a validated op stream into `ops[*n_ops..]`.  Nested OPT/ALT
// bodies decode recursively into the same array directly after their parent
// op: OPT stores its child count in .b; ALT stores its branch count in .a
// and each branch is a BRANCH marker (kind 9) whose .b is that branch's op
// count.  Returns the number of ops in THIS stream (excluding descendants'
// entries... callers use the returned count plus each child's subtree size
// via .d = total subtree ops).  Returns -1 when the buffer is exceeded.
int32_t t1_decode_into(const int32_t* w, int64_t nw, T1DecOp* ops,
                       int32_t* n_ops);

// Fuse CAPSTART/SPAN/CAPEND[/LIT] → FIELD over a just-decoded flat RANGE
// [from, *n_ops) that contains no nested ops (caller guarantees).
static void t1_fuse_range(T1DecOp* ops, int32_t from, int32_t* n_ops) {
    int32_t out = from;
    int32_t n = *n_ops;
    for (int32_t k = from; k < n;) {
        if (k + 2 < n && ops[k].kind == 3 && ops[k + 1].kind == 1 &&
            ops[k + 2].kind == 4 && ops[k].a == ops[k + 2].a) {
            T1DecOp f;
            f.kind = 7;
            f.a = ops[k].a;          // cap id
            f.b = ops[k + 1].a;      // class
            f.c2 = ops[k + 1].b;     // min
            f.d = ops[k + 1].c2;     // max
            f.lit = -1;
            f.mask = nullptr;
            f.w = nullptr;
            f.wn = 0;
            k += 3;
            if (k < n && ops[k].kind == 0) {
                f.lit = ops[k].a;
                ++k;
            }
            ops[out++] = f;
        } else {
            ops[out++] = ops[k++];
        }
    }
    *n_ops = out;
}

int32_t t1_decode_into(const int32_t* w, int64_t nw, T1DecOp* ops,
                       int32_t* n_ops) {
    int64_t i = 0;
    int32_t flat_from = *n_ops;   // start of the current fuse window
    while (i < nw) {
        if (*n_ops >= kT1MaxDecOps) return -1;
        switch (w[i]) {
        case 0: {
            T1DecOp& o = ops[(*n_ops)++];
            o.kind = 0; o.a = w[i + 1]; o.lit = -1; o.mask = nullptr; i += 2;
            break;
        }
        case 1: {
            T1DecOp& o = ops[(*n_ops)++];
            o.kind = 1; o.a = w[i + 1]; o.b = w[i + 2]; o.c2 = w[i + 3];
            o.lit = -1; o.mask = nullptr;
            i += 5;
            break;
        }
        case 2: {
            T1DecOp& o = ops[(*n_ops)++];
            o.kind = 2; o.a = w[i + 1]; o.b = w[i + 2]; o.lit = -1; o.mask = nullptr; i += 3;
            break;
        }
        case 3:
        case 4: {
            T1DecOp& o = ops[(*n_ops)++];
            o.kind = w[i]; o.a = w[i + 1]; o.lit = -1; o.mask = nullptr; i += 2;
            break;
        }
        case 5: {
            // fuse the flat run so far, then decode the body inline
            t1_fuse_range(ops, flat_from, n_ops);
            int32_t self = (*n_ops)++;
            if (self >= kT1MaxDecOps) return -1;
            ops[self].kind = 5;
            ops[self].lit = -1; ops[self].mask = nullptr;
            int32_t bw = w[i + 1];
            int32_t child_from = *n_ops;
            if (t1_decode_into(w + i + 2, bw, ops, n_ops) < 0) return -1;
            ops[self].b = *n_ops - child_from;   // children (subtree) size
            i += 2 + bw;
            flat_from = *n_ops;
            break;
        }
        case 6: {
            int32_t nb = w[i + 1];
            int64_t j = i + 2;
            bool all_lit = true;
            for (int32_t b = 0; b < nb; ++b) {
                if (w[j] != 2 || w[j + 1] != 0) all_lit = false;
                j += 1 + w[j];
            }
            if (all_lit) {
                // all-literal alternation (grok MONTH/LOGLEVEL style):
                // first matching literal wins — no trial state copies
                T1DecOp& o = ops[(*n_ops)++];
                o.kind = 8;
                o.lit = -1; o.mask = nullptr;
                o.w = w + i;
                o.wn = (int32_t)(j - i);
                i = j;
                break;
            }
            t1_fuse_range(ops, flat_from, n_ops);
            int32_t self = (*n_ops)++;
            if (self >= kT1MaxDecOps) return -1;
            ops[self].kind = 6;
            ops[self].a = nb;
            ops[self].lit = -1; ops[self].mask = nullptr;
            j = i + 2;
            for (int32_t b = 0; b < nb; ++b) {
                int32_t marker = (*n_ops)++;
                if (marker >= kT1MaxDecOps) return -1;
                ops[marker].kind = 9;   // BRANCH
                ops[marker].lit = -1; ops[marker].mask = nullptr;
                int32_t bw = w[j];
                int32_t child_from = *n_ops;
                if (t1_decode_into(w + j + 1, bw, ops, n_ops) < 0)
                    return -1;
                ops[marker].b = *n_ops - child_from;
                j += 1 + bw;
            }
            ops[self].b = *n_ops - self - 1;   // whole subtree size
            i = j;
            flat_from = *n_ops;
            break;
        }
        default:
            return -1;
        }
    }
    t1_fuse_range(ops, flat_from, n_ops);
    return *n_ops;
}

int32_t t1_decode(const int32_t* w, int64_t nw, T1DecOp* ops) {
    int32_t n = 0;
    if (t1_decode_into(w, nw, ops, &n) < 0) return -1;
    // Specialize capture-free ALT/OPT: their trials touch nothing but
    // st.cur, so the per-branch T1State copies (3 × ncaps ints each) are
    // pure waste.  Bodies may contain LIT/FIXED/SPAN and NESTED capture-
    // free ALT/OPT (grok time composites are several levels deep:
    // `(?::(?:[0-5][0-9]|60)(?:[:.,][0-9]+)?)?`); anything touching
    // captures keeps the generic trial machinery.  Innermost shapes
    // specialize first because the scan runs left-to-right and bodies
    // follow their parent op, so a parent sees its children's rewritten
    // kinds... except a parent PRECEDES its body in the decoded layout —
    // hence the fixpoint loop (depth ≤ kT1MaxDecOps, converges in
    // nesting-depth passes, tiny in practice).
    auto body_simple = [&](int32_t from, int32_t count) {
        for (int32_t k = from; k < from + count;) {
            int32_t kind = ops[k].kind;
            if (kind == 0 || kind == 1 || kind == 2 || kind == 8) {
                ++k;                            // LIT / SPAN / FIXED / LITALT
            } else if (kind == 10 || kind == 11) {
                k += 1 + ops[k].b;              // nested simple subtree
            } else {
                return false;
            }
        }
        return true;
    };
    // reverse scan: every body FOLLOWS its parent op in the decoded
    // layout, so walking backwards rewrites all descendants before their
    // parent — one pass, no fixpoint
    for (int32_t i = n - 1; i >= 0; --i) {
        if (ops[i].kind == 5 && body_simple(i + 1, ops[i].b)) {
            ops[i].kind = 11;                   // SIMPLEOPT
        } else if (ops[i].kind == 6) {
            bool all = true;
            int32_t bi = i + 1;
            for (int32_t b = 0; b < ops[i].a && all; ++b) {
                if (ops[bi].kind != 9 ||
                    !body_simple(bi + 1, ops[bi].b)) all = false;
                bi += 1 + ops[bi].b;
            }
            if (all) ops[i].kind = 10;          // SIMPLEALT
        }
    }
    return n;
}

// Capture-free body walk: advances *cur on success, touches nothing else.
// Handles LIT/FIXED/SPAN and NESTED capture-free ALT/OPT — a failed trial
// at any depth leaves the caller's cursor untouched (locals only, zero
// T1State copies).
static bool t1_walk_simple(const T1Ctx& c, const T1DecOp* ops,
                           int32_t from, int32_t count, int32_t* cur) {
    int32_t p = *cur;
    for (int32_t k = from; k < from + count;) {
        const T1DecOp& q = ops[k];
        switch (q.kind) {
        case 0:
            if (!t1_lit_at(c, q.a, p)) return false;
            p += c.lit_lens[q.a];
            ++k;
            break;
        case 1: {  // SPAN (maximal munch, follow-disjoint by compilation)
            int32_t end = (q.mask != nullptr && c.mask_base != nullptr)
                              ? t1_mask_find(q.mask, c.mask_words, p)
                              : t1_scan_fwd(c, q.a, p);
            int32_t run = end - p;
            if (run < q.b || (q.c2 >= 0 && run > q.c2)) return false;
            p = end;
            ++k;
            break;
        }
        case 2:    // FIXED
            if (p + q.b > c.len) return false;
            for (int32_t j = 0; j < q.b; ++j)
                if (!t1_member(c, q.a, c.row[p + j])) return false;
            p += q.b;
            ++k;
            break;
        case 8: {  // all-literal ALT: first literal matching at p wins
            const int32_t* aw = q.w;
            int32_t nb = aw[1];
            const int32_t* br = aw + 2;  // per branch: [bw=2, 0, lit_idx]
            bool hit = false;
            for (int32_t b = 0; b < nb; ++b, br += 3) {
                int32_t li = br[2];
                if (t1_lit_at(c, li, p)) {
                    p += c.lit_lens[li];
                    hit = true;
                    break;
                }
            }
            if (!hit) return false;
            ++k;
            break;
        }
        case 11:   // nested SIMPLEOPT
            t1_walk_simple(c, ops, k + 1, q.b, &p);
            k += 1 + q.b;
            break;
        case 10: {  // nested SIMPLEALT: first matching branch wins
            int32_t end = k + 1 + q.b;
            int32_t bi = k + 1;
            bool chosen = false;
            for (int32_t b = 0; b < q.a; ++b) {
                int32_t bn = ops[bi].b;
                if (!chosen && t1_walk_simple(c, ops, bi + 1, bn, &p))
                    chosen = true;
                bi += 1 + bn;
            }
            if (!chosen) return false;
            k = end;
            break;
        }
        default:
            return false;  // unreachable: body_simple gates the shapes
        }
    }
    *cur = p;
    return true;
}

void t1_exec_dec(const T1Ctx& c, const T1DecOp* ops, int32_t from,
                 int32_t to, T1State& st) {
    for (int32_t oi = from; oi < to; ++oi) {
        const T1DecOp& o = ops[oi];
        switch (o.kind) {
        case 7: {  // FIELD
            const T1ClassInfo& ci = c.cinfo[o.b];
            int32_t start = st.cur;
            int32_t end;
            if (o.mask != nullptr && c.mask_base != nullptr) {
                end = t1_mask_find(o.mask, c.mask_words, start);
            } else if (o.lit >= 0 && ci.neg_char >= 0 &&
                c.lit_blob[c.lit_offs[o.lit]] == (uint8_t)ci.neg_char) {
                const void* hit =
                    memchr(c.row + start, ci.neg_char, c.len - start);
                if (!hit) { st.ok = false; return; }
                end = (int32_t)((const uint8_t*)hit - c.row);
            } else {
                end = t1_scan_fwd(c, o.b, start);
            }
            int32_t run = end - start;
            if (run < o.c2 || (o.d >= 0 && run > o.d)) {
                st.ok = false;
                return;
            }
            st.cap_off[o.a] = start;
            st.cap_len[o.a] = run;
            st.cur = end;
            if (o.lit >= 0) {
                if (!t1_lit_at(c, o.lit, end)) { st.ok = false; return; }
                st.cur = end + c.lit_lens[o.lit];
            }
            break;
        }
        case 0:
            if (!t1_lit_at(c, o.a, st.cur)) { st.ok = false; return; }
            st.cur += c.lit_lens[o.a];
            break;
        case 1: {  // SPAN
            int32_t end = (o.mask != nullptr && c.mask_base != nullptr)
                              ? t1_mask_find(o.mask, c.mask_words, st.cur)
                              : t1_scan_fwd(c, o.a, st.cur);
            int32_t run = end - st.cur;
            if (run < o.b || (o.c2 >= 0 && run > o.c2)) {
                st.ok = false;
                return;
            }
            st.cur = end;
            break;
        }
        case 2: {  // FIXED
            if (st.cur + o.b > c.len) { st.ok = false; return; }
            for (int32_t j = 0; j < o.b; ++j)
                if (!t1_member(c, o.a, c.row[st.cur + j])) {
                    st.ok = false;
                    return;
                }
            st.cur += o.b;
            break;
        }
        case 3:
            st.cap_start[o.a] = st.cur;
            break;
        case 4:
            st.cap_off[o.a] = st.cap_start[o.a];
            st.cap_len[o.a] = st.cur - st.cap_start[o.a];
            break;
        case 8: {  // all-literal ALT: first literal matching at cur wins
            const int32_t* aw = o.w;
            int32_t nb = aw[1];
            const int32_t* q = aw + 2;   // per branch: [bw=2, 0, lit_idx]
            bool hit = false;
            for (int32_t b = 0; b < nb; ++b, q += 3) {
                int32_t li = q[2];
                if (t1_lit_at(c, li, st.cur)) {
                    st.cur += c.lit_lens[li];
                    hit = true;
                    break;
                }
            }
            if (!hit) { st.ok = false; return; }
            break;
        }
        case 5: {  // OPT: children decoded inline right after this op
            T1State save;
            t1_copy(save, st, c.ncaps);
            t1_exec_dec(c, ops, oi + 1, oi + 1 + o.b, st);
            if (!st.ok) t1_copy(st, save, c.ncaps);  // save.ok was true
            oi += o.b;
            break;
        }
        case 11: {  // SIMPLEOPT: capture-free optional, no state copies
            t1_walk_simple(c, ops, oi + 1, o.b, &st.cur);
            oi += o.b;
            break;
        }
        case 10: {  // SIMPLEALT: capture-free branches, first match wins
            int32_t end = oi + 1 + o.b;
            int32_t bi = oi + 1;
            bool chosen = false;
            for (int32_t b = 0; b < o.a; ++b) {
                int32_t bn = ops[bi].b;
                if (!chosen && t1_walk_simple(c, ops, bi + 1, bn, &st.cur))
                    chosen = true;
                bi += 1 + bn;
            }
            oi = end - 1;
            if (!chosen) { st.ok = false; return; }
            break;
        }
        case 6: {  // ALT: BRANCH markers + bodies decoded inline
            T1State before;
            t1_copy(before, st, c.ncaps);
            int32_t end = oi + 1 + o.b;
            int32_t bi = oi + 1;
            bool chosen = false;
            for (int32_t b = 0; b < o.a; ++b) {
                int32_t bn = ops[bi].b;
                if (!chosen) {
                    T1State trial;
                    t1_copy(trial, before, c.ncaps);
                    t1_exec_dec(c, ops, bi + 1, bi + 1 + bn, trial);
                    if (trial.ok) {
                        t1_copy(st, trial, c.ncaps);
                        chosen = true;
                    }
                }
                bi += 1 + bn;
            }
            oi = end - 1;
            if (!chosen) { st.ok = false; return; }
            break;
        }
        default:
            st.ok = false;  // unreachable with a well-formed decode
            return;
        }
    }
}

}  // namespace

extern "C" {

// Returns 0 on success, -1 on malformed program.  cap_off/cap_len are
// [n, num_caps] row-major; offsets written arena-ABSOLUTE (matched rows'
// absent captures get off=row_origin, len=-1, matching the device path
// after origin addition in engine.parse_batch).
int64_t lct_t1_exec(const uint8_t* arena, int64_t arena_len,
                    const int64_t* offsets, const int32_t* lengths, int64_t n,
                    const int32_t* words, int64_t n_words,
                    const uint8_t* classes, int64_t n_classes,
                    const uint8_t* lit_blob, const int32_t* lit_offs,
                    const int32_t* lit_lens, int64_t n_lits, uint8_t* ok_out,
                    int32_t* cap_off_out, int32_t* cap_len_out) {
    T1Header h{};
    if (!t1_parse_header(words, n_words, n_classes, lit_lens, n_lits, h))
        return -1;
    const int32_t C = h.num_caps;

    // derive per-class scan accelerators from the membership tables
    T1ClassInfo cinfo[kT1MaxClasses];
    if (n_classes > kT1MaxClasses) return -1;
    for (int64_t k = 0; k < n_classes; ++k) {
        const uint8_t* tbl = classes + k * 256;
        T1ClassInfo& ci = cinfo[k];
        memset(ci.tr_lo, 0, 16);
        memset(ci.tr_hi, 0, 16);
        int32_t non = -1, n_non = 0;
        bool hi = true;
        for (int32_t b = 0; b < 256; ++b) {
            if (!tbl[b]) {
                ++n_non;
                non = b;
                if (b >= 0x21) hi = false;
            } else {
                int32_t lo_nib = b & 15, hi_nib = b >> 4;
                if (hi_nib < 8)
                    ci.tr_lo[lo_nib] |= (uint8_t)(1 << hi_nib);
                else
                    ci.tr_hi[lo_nib] |= (uint8_t)(1 << (hi_nib - 8));
            }
        }
        ci.neg_char = (n_non == 1) ? non : -1;
        ci.hi_member = hi;
    }

    // decode + fuse the prefix once per batch; -1 ⇒ interpreter fallback
    T1DecOp dec[kT1MaxDecOps];
    int32_t n_dec = t1_decode(h.prefix, h.prefix_n, dec);

    // full coverage: a linear decoded program (no OPT/ALT, no pivots) whose
    // FIELD/CAPEND ops unconditionally write every capture slot — per-row
    // capture init can then be skipped entirely
    bool full_cov = false;
    if (n_dec >= 0 && !h.has_pivot && !h.has_pivot2 && C <= 32) {
        uint64_t covered = 0;
        bool simple = true;
        for (int32_t k = 0; k < n_dec; ++k) {
            if (dec[k].kind == 7 || dec[k].kind == 4)
                covered |= 1ull << dec[k].a;
            else if (dec[k].kind == 5 || dec[k].kind == 6)
                simple = false;  // kind 8 (LITALT) never touches captures
        }
        full_cov = simple && covered == ((1ull << C) - 1);
    }

    T1Ctx ctx{nullptr, 0, classes, lit_blob, lit_offs, lit_lens, cinfo, C,
              nullptr, nullptr, 0, 0};

    // Stop-mask acceleration: linear decoded programs only (pivot paths
    // scan backwards; OPT/ALT re-scan from trial states — both keep the
    // classic scanners).  Slot-assign every class used by SPAN/FIELD ops;
    // per row one vector sweep fills the masks and every scan becomes a
    // word lookup + ctz.
    constexpr int32_t kMaskStride = 32;            // words → 2048-byte rows
    int8_t mask_slot[kT1MaxClasses];
    uint64_t maskbuf[kT1MaskSlots * kMaskStride];
    T1MaskPlan plan{};
    bool masks_on = false;
    if (g_has_avx2 && n_dec >= 0 && !h.has_pivot && !h.has_pivot2) {
        memset(mask_slot, -1, sizeof(mask_slot));
        bool overflow = false;
        for (int32_t k = 0; k < n_dec && !overflow; ++k) {
            int32_t cls = -1;
            if (dec[k].kind == 1) cls = dec[k].a;        // SPAN
            else if (dec[k].kind == 7) cls = dec[k].b;   // FIELD
            if (cls < 0 || mask_slot[cls] >= 0) continue;
            if (plan.n_slots >= kT1MaskSlots) { overflow = true; break; }
            mask_slot[cls] = (int8_t)plan.n_slots;
            plan.ci[plan.n_slots] = &cinfo[cls];
            plan.tbl[plan.n_slots] = classes + (int64_t)cls * 256;
            ++plan.n_slots;
        }
        masks_on = !overflow && plan.n_slots > 0;
        if (masks_on) {
            // resolve each op's mask row once; the per-row sweep refills
            // the same buffer so the pointers stay valid for every row
            for (int32_t k = 0; k < n_dec; ++k) {
                int32_t cls = dec[k].kind == 1 ? dec[k].a
                              : dec[k].kind == 7 ? dec[k].b : -1;
                if (cls >= 0 && mask_slot[cls] >= 0)
                    dec[k].mask =
                        maskbuf + (int64_t)mask_slot[cls] * kMaskStride;
            }
        }
    }

    for (int64_t r = 0; r < n; ++r) {
        int64_t off = offsets[r];
        int64_t len = lengths[r];
        if (len < 0) len = 0;
        bool row_ok = false;
        T1State final_st;
        T1State st;
        const T1State* outst = &final_st;
        if (off >= 0 && off + len <= arena_len && len <= INT32_MAX) {
            ctx.row = arena + off;
            ctx.len = (int32_t)len;
            if (masks_on && len < kMaskStride * 64) {
                // strict <: a row of exactly stride*64 bytes would have no
                // sealed stop bit at index len (and a scan starting there
                // would read one word past the slot) — classic scanners
                // handle it instead
                int32_t nw = (int32_t)((len + 64) >> 6);  // ≥1, covers seal
                if (nw > kMaskStride) nw = kMaskStride;
                t1_mask_build_all(ctx.row, ctx.len, arena_len - off, plan,
                                  maskbuf, kMaskStride, nw);
                for (int32_t s = 0; s < plan.n_slots; ++s)
                    t1_mask_seal(maskbuf + (int64_t)s * kMaskStride, nw,
                                 ctx.len);
                ctx.mask_slot = mask_slot;
                ctx.mask_base = maskbuf;
                ctx.mask_words = nw;
                ctx.mask_stride = kMaskStride;
            } else {
                ctx.mask_base = nullptr;
            }
            st.cur = 0;
            st.ok = true;
            if (!full_cov) {
                for (int32_t k = 0; k < C; ++k) {
                    st.cap_off[k] = 0;
                    st.cap_len[k] = -1;
                    st.cap_start[k] = 0;
                }
            }
            if (n_dec >= 0)
                t1_exec_dec(ctx, dec, 0, n_dec, st);
            else
                t1_emit(ctx, h.prefix, h.prefix_n, st);
            if (h.has_pivot2) {
                if (st.ok) {
                    T1State rst;
                    t1_copy(rst, st, C);
                    rst.cur = ctx.len;
                    int32_t floor_ =
                        st.cur + h.p1_min + h.mid_fixed + h.p2_min;
                    t1_emit_rev(ctx, h.suffix, h.suffix_n, rst, floor_);
                    if (rst.ok) {
                        int32_t lo1 = st.cur, hi2 = rst.cur;
                        int32_t p_lo = lo1 + h.p1_min;
                        int32_t p_hi = hi2 - h.mid_fixed - h.p2_min;
                        if (p_lo < 0) p_lo = 0;
                        int32_t p = -1;
                        const uint8_t* lit = lit_blob + lit_offs[h.mid_lit_idx];
                        if (h.p1_lazy) {  // both lazy: first occurrence
                            for (int32_t q = p_lo; q <= p_hi; ++q)
                                if (memcmp(ctx.row + q, lit, h.mid_fixed) ==
                                    0) {
                                    p = q;
                                    break;
                                }
                        } else {  // both greedy: last occurrence
                            for (int32_t q = p_hi; q >= p_lo; --q)
                                if (memcmp(ctx.row + q, lit, h.mid_fixed) ==
                                    0) {
                                    p = q;
                                    break;
                                }
                        }
                        if (p >= 0) {
                            st.cur = p;
                            t1_emit(ctx, h.mid, h.mid_n, st);
                            int32_t lo2 = st.cur;
                            if (st.ok && hi2 >= lo2 && p - lo1 >= h.p1_min &&
                                hi2 - lo2 >= h.p2_min &&
                                t1_all_member(ctx, h.p1_cls, lo1, p) &&
                                t1_all_member(ctx, h.p2_cls, lo2, hi2)) {
                                row_ok = true;
                                t1_copy(final_st, rst, C);
                                for (int32_t k = 0; k < h.n_mid_end; ++k) {
                                    int32_t id = h.mid_end_ids[k];
                                    final_st.cap_off[id] = st.cap_off[id];
                                    final_st.cap_len[id] = st.cap_len[id];
                                }
                                for (int32_t k = 0; k < h.n_split; ++k) {
                                    int32_t id = h.split_ids[k];
                                    final_st.cap_off[id] = st.cap_start[id];
                                    final_st.cap_len[id] =
                                        rst.cap_start[id] - st.cap_start[id];
                                }
                            }
                        }
                    }
                }
            } else if (h.has_pivot) {
                if (st.ok) {
                    T1State rst;
                    t1_copy(rst, st, C);
                    rst.cur = ctx.len;
                    t1_emit_rev(ctx, h.suffix, h.suffix_n, rst,
                                st.cur + h.p1_min);
                    if (rst.ok && rst.cur >= st.cur) {
                        int32_t run = rst.cur - st.cur;
                        if (run >= h.p1_min &&
                            (h.p1_max < 0 || run <= h.p1_max) &&
                            t1_all_member(ctx, h.p1_cls, st.cur, rst.cur)) {
                            row_ok = true;
                            t1_copy(final_st, rst, C);
                            for (int32_t k = 0; k < h.n_split; ++k) {
                                int32_t id = h.split_ids[k];
                                final_st.cap_off[id] = st.cap_start[id];
                                final_st.cap_len[id] =
                                    rst.cap_start[id] - st.cap_start[id];
                            }
                        }
                    }
                }
            } else {
                row_ok = st.ok && st.cur == ctx.len;
                outst = &st;  // no pivot: emit straight from the walk state
            }
        }
        ok_out[r] = row_ok ? 1 : 0;
        int32_t* co = cap_off_out + r * C;
        int32_t* cl = cap_len_out + r * C;
        if (row_ok) {
            for (int32_t k = 0; k < C; ++k) {
                co[k] = (int32_t)off + outst->cap_off[k];
                cl[k] = outst->cap_len[k];
            }
        } else {
            for (int32_t k = 0; k < C; ++k) {
                co[k] = (int32_t)off;
                cl[k] = -1;
            }
        }
    }
    return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// AES-CBC encryption (processor_encrypt).
//
// The reference encrypts fields with Go's crypto/aes CBC + PKCS7
// (plugins/processor/encrypt/processor_encrypt.go); this runtime has no
// Python crypto package, so AES lives here.  Encrypt-only (the agent never
// decrypts); key sizes 16/24/32; caller pads to a block multiple.
// Validated against the NIST SP 800-38A CBC known-answer vectors in
// tests/test_longtail_processors.py.
// ---------------------------------------------------------------------------

namespace {

const uint8_t kSbox[256] = {
    0x63, 0x7c, 0x77, 0x7b, 0xf2, 0x6b, 0x6f, 0xc5, 0x30, 0x01, 0x67, 0x2b,
    0xfe, 0xd7, 0xab, 0x76, 0xca, 0x82, 0xc9, 0x7d, 0xfa, 0x59, 0x47, 0xf0,
    0xad, 0xd4, 0xa2, 0xaf, 0x9c, 0xa4, 0x72, 0xc0, 0xb7, 0xfd, 0x93, 0x26,
    0x36, 0x3f, 0xf7, 0xcc, 0x34, 0xa5, 0xe5, 0xf1, 0x71, 0xd8, 0x31, 0x15,
    0x04, 0xc7, 0x23, 0xc3, 0x18, 0x96, 0x05, 0x9a, 0x07, 0x12, 0x80, 0xe2,
    0xeb, 0x27, 0xb2, 0x75, 0x09, 0x83, 0x2c, 0x1a, 0x1b, 0x6e, 0x5a, 0xa0,
    0x52, 0x3b, 0xd6, 0xb3, 0x29, 0xe3, 0x2f, 0x84, 0x53, 0xd1, 0x00, 0xed,
    0x20, 0xfc, 0xb1, 0x5b, 0x6a, 0xcb, 0xbe, 0x39, 0x4a, 0x4c, 0x58, 0xcf,
    0xd0, 0xef, 0xaa, 0xfb, 0x43, 0x4d, 0x33, 0x85, 0x45, 0xf9, 0x02, 0x7f,
    0x50, 0x3c, 0x9f, 0xa8, 0x51, 0xa3, 0x40, 0x8f, 0x92, 0x9d, 0x38, 0xf5,
    0xbc, 0xb6, 0xda, 0x21, 0x10, 0xff, 0xf3, 0xd2, 0xcd, 0x0c, 0x13, 0xec,
    0x5f, 0x97, 0x44, 0x17, 0xc4, 0xa7, 0x7e, 0x3d, 0x64, 0x5d, 0x19, 0x73,
    0x60, 0x81, 0x4f, 0xdc, 0x22, 0x2a, 0x90, 0x88, 0x46, 0xee, 0xb8, 0x14,
    0xde, 0x5e, 0x0b, 0xdb, 0xe0, 0x32, 0x3a, 0x0a, 0x49, 0x06, 0x24, 0x5c,
    0xc2, 0xd3, 0xac, 0x62, 0x91, 0x95, 0xe4, 0x79, 0xe7, 0xc8, 0x37, 0x6d,
    0x8d, 0xd5, 0x4e, 0xa9, 0x6c, 0x56, 0xf4, 0xea, 0x65, 0x7a, 0xae, 0x08,
    0xba, 0x78, 0x25, 0x2e, 0x1c, 0xa6, 0xb4, 0xc6, 0xe8, 0xdd, 0x74, 0x1f,
    0x4b, 0xbd, 0x8b, 0x8a, 0x70, 0x3e, 0xb5, 0x66, 0x48, 0x03, 0xf6, 0x0e,
    0x61, 0x35, 0x57, 0xb9, 0x86, 0xc1, 0x1d, 0x9e, 0xe1, 0xf8, 0x98, 0x11,
    0x69, 0xd9, 0x8e, 0x94, 0x9b, 0x1e, 0x87, 0xe9, 0xce, 0x55, 0x28, 0xdf,
    0x8c, 0xa1, 0x89, 0x0d, 0xbf, 0xe6, 0x42, 0x68, 0x41, 0x99, 0x2d, 0x0f,
    0xb0, 0x54, 0xbb, 0x16};

inline uint8_t xtime(uint8_t x) {
    return (uint8_t)((x << 1) ^ ((x & 0x80) ? 0x1b : 0x00));
}

struct AesKey {
    uint8_t round_keys[15 * 16];
    int rounds;
};

bool aes_expand_key(const uint8_t* key, int key_len, AesKey& out) {
    int nk;
    if (key_len == 16) {
        nk = 4;
        out.rounds = 10;
    } else if (key_len == 24) {
        nk = 6;
        out.rounds = 12;
    } else if (key_len == 32) {
        nk = 8;
        out.rounds = 14;
    } else {
        return false;
    }
    int total_words = 4 * (out.rounds + 1);
    uint8_t* w = out.round_keys;
    memcpy(w, key, key_len);
    uint8_t rcon = 1;
    for (int i = nk; i < total_words; ++i) {
        uint8_t t[4];
        memcpy(t, w + (i - 1) * 4, 4);
        if (i % nk == 0) {
            uint8_t tmp = t[0];
            t[0] = (uint8_t)(kSbox[t[1]] ^ rcon);
            t[1] = kSbox[t[2]];
            t[2] = kSbox[t[3]];
            t[3] = kSbox[tmp];
            rcon = xtime(rcon);
        } else if (nk > 6 && i % nk == 4) {
            for (int j = 0; j < 4; ++j) t[j] = kSbox[t[j]];
        }
        for (int j = 0; j < 4; ++j)
            w[i * 4 + j] = (uint8_t)(w[(i - nk) * 4 + j] ^ t[j]);
    }
    return true;
}

void aes_encrypt_block(const AesKey& k, uint8_t* s) {
    for (int j = 0; j < 16; ++j) s[j] ^= k.round_keys[j];
    for (int round = 1; round <= k.rounds; ++round) {
        // SubBytes
        for (int j = 0; j < 16; ++j) s[j] = kSbox[s[j]];
        // ShiftRows
        uint8_t t;
        t = s[1]; s[1] = s[5]; s[5] = s[9]; s[9] = s[13]; s[13] = t;
        t = s[2]; s[2] = s[10]; s[10] = t;
        t = s[6]; s[6] = s[14]; s[14] = t;
        t = s[15]; s[15] = s[11]; s[11] = s[7]; s[7] = s[3]; s[3] = t;
        // MixColumns (skipped on the final round)
        if (round != k.rounds) {
            for (int c = 0; c < 4; ++c) {
                uint8_t* p = s + 4 * c;
                uint8_t a0 = p[0], a1 = p[1], a2 = p[2], a3 = p[3];
                uint8_t all = (uint8_t)(a0 ^ a1 ^ a2 ^ a3);
                p[0] = (uint8_t)(a0 ^ all ^ xtime((uint8_t)(a0 ^ a1)));
                p[1] = (uint8_t)(a1 ^ all ^ xtime((uint8_t)(a1 ^ a2)));
                p[2] = (uint8_t)(a2 ^ all ^ xtime((uint8_t)(a2 ^ a3)));
                p[3] = (uint8_t)(a3 ^ all ^ xtime((uint8_t)(a3 ^ a0)));
            }
        }
        for (int j = 0; j < 16; ++j)
            s[j] ^= k.round_keys[round * 16 + j];
    }
}

}  // namespace

extern "C" {

// data_len must be a multiple of 16 (caller applies PKCS7).
// Returns 0 on success, -1 on bad key size / length.
int64_t lct_aes_cbc_encrypt(const uint8_t* key, int64_t key_len,
                            const uint8_t* iv, const uint8_t* data,
                            int64_t data_len, uint8_t* out) {
    AesKey k;
    if (!aes_expand_key(key, (int)key_len, k)) return -1;
    if (data_len % 16 != 0) return -1;
    uint8_t prev[16];
    memcpy(prev, iv, 16);
    for (int64_t off = 0; off < data_len; off += 16) {
        uint8_t block[16];
        for (int j = 0; j < 16; ++j)
            block[j] = (uint8_t)(data[off + j] ^ prev[j]);
        aes_encrypt_block(k, block);
        memcpy(out + off, block, 16);
        memcpy(prev, block, 16);
    }
    return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// loongstruct: structural-index parsing plane (JSON + quote-mode delimiter).
//
// ParPaRaw's formulation (PAPERS.md): classify the raw buffer into per-bit
// structural bitmaps with branch-free whole-word passes, then derive field
// spans from the index instead of walking bytes with a per-row state
// machine.  Stage 1 (per row):
//
//   backslash / quote / structural-char / control-char masks
//     64 bytes per step (AVX2 compare+movemask; scalar table fallback)
//   escaped mask
//     simdjson's odd-length backslash-run carry trick: odd-run *ends* are
//     the escaped positions, with a 1-bit carry across 64-bit words so
//     backslash runs crossing word boundaries resolve exactly
//   in-string mask
//     prefix-XOR (carry-less multiply by all-ones, as the 6-step SWAR
//     shift cascade) over unescaped quotes, sign-propagated across words;
//     the mask is INCLUSIVE: the opening quote and the string body are
//     inside, the closing quote is outside
//   structural index
//     positions of (structural & ~in_string) | unescaped quotes, emitted
//     in order via ctz iteration — the only per-byte-ish loop left, and
//     it steps per *structural character*, not per byte
//
// Stage 2 walks the position index: a recursive-descent JSON validator /
// span emitter (grammar-complete, so acceptance matches Python's json
// module: anything the index walk cannot prove well-formed is flagged for
// the counted per-row fallback) and a CSV walk that reproduces the
// DelimiterModeFsmParser state table field-for-field at
// structural-character granularity.  Values that need byte rewrites
// (JSON escape sequences, CSV doubled quotes / quoted-then-tail fields)
// are decoded into a caller-provided side arena exactly once; their spans
// are emitted with offset >= arena_len (side sentinel) for the caller's
// vectorised fix-up.
// ---------------------------------------------------------------------------

#include <cstdlib>

namespace {

struct BlockMasks {
    uint64_t bs;          // escape character
    uint64_t quote;
    uint64_t structural;  // {}[]:, for JSON; the separator for delimiter
    uint64_t ctrl;        // bytes < 0x20
    uint64_t ws;          // JSON whitespace: space \t \n \r
};

// Scalar classifier: correctness floor for non-AVX2 hosts; the tail mask
// is applied by the caller (shared with the AVX2 path).
static void classify_block_scalar(const uint8_t* p, int esc_ch, int quote_ch,
                                  const uint8_t* struct_tbl,
                                  BlockMasks* out) {
    uint64_t bs = 0, q = 0, st = 0, ct = 0, ws = 0;
    for (int j = 0; j < 64; ++j) {
        uint8_t c = p[j];
        uint64_t b = 1ULL << j;
        if ((int)c == esc_ch) bs |= b;
        if ((int)c == quote_ch) q |= b;
        if (struct_tbl[c]) st |= b;
        if (c < 0x20) ct |= b;
        if (c == ' ' || c == '\t' || c == '\n' || c == '\r') ws |= b;
    }
    out->bs = bs; out->quote = q; out->structural = st; out->ctrl = ct;
    out->ws = ws;
}

#if defined(__x86_64__)
__attribute__((target("avx512bw,avx512f")))
static void classify_block_avx512(const uint8_t* p, int64_t nbytes,
                                  int esc_ch, int quote_ch,
                                  int mode_json, int sep_ch,
                                  BlockMasks* out) {
    // masked load: the row tail needs no padded staging copy — lanes
    // beyond nbytes read as zero without touching memory
    __mmask64 lanes = nbytes >= 64 ? ~0ULL : ((1ULL << nbytes) - 1);
    __m512i v = _mm512_maskz_loadu_epi8(lanes, (const void*)p);
    out->bs = esc_ch >= 0
        ? _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8((char)esc_ch)) : 0;
    out->quote = quote_ch >= 0
        ? _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8((char)quote_ch)) : 0;
    if (mode_json) {
        out->structural =
              _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8('{'))
            | _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8('}'))
            | _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8('['))
            | _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8(']'))
            | _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8(':'))
            | _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8(','));
    } else {
        out->structural =
            _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8((char)sep_ch));
    }
    out->ctrl = _mm512_cmplt_epu8_mask(v, _mm512_set1_epi8(0x20));
    out->ws = _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8(' '))
            | _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8('\t'))
            | _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8('\n'))
            | _mm512_cmpeq_epi8_mask(v, _mm512_set1_epi8('\r'));
}

__attribute__((target("avx2")))
static inline uint64_t mm_eq64(__m256i lo, __m256i hi, uint8_t c) {
    __m256i v = _mm256_set1_epi8((char)c);
    uint32_t m0 = (uint32_t)_mm256_movemask_epi8(_mm256_cmpeq_epi8(lo, v));
    uint32_t m1 = (uint32_t)_mm256_movemask_epi8(_mm256_cmpeq_epi8(hi, v));
    return (uint64_t)m0 | ((uint64_t)m1 << 32);
}

__attribute__((target("avx2")))
static void classify_block_avx2(const uint8_t* p, int esc_ch, int quote_ch,
                                int mode_json, int sep_ch, BlockMasks* out) {
    __m256i lo = _mm256_loadu_si256((const __m256i*)(const void*)p);
    __m256i hi = _mm256_loadu_si256((const __m256i*)(const void*)(p + 32));
    out->bs = esc_ch >= 0 ? mm_eq64(lo, hi, (uint8_t)esc_ch) : 0;
    out->quote = quote_ch >= 0 ? mm_eq64(lo, hi, (uint8_t)quote_ch) : 0;
    if (mode_json) {
        out->structural = mm_eq64(lo, hi, '{') | mm_eq64(lo, hi, '}')
                        | mm_eq64(lo, hi, '[') | mm_eq64(lo, hi, ']')
                        | mm_eq64(lo, hi, ':') | mm_eq64(lo, hi, ',');
    } else {
        out->structural = mm_eq64(lo, hi, (uint8_t)sep_ch);
    }
    __m256i t = _mm256_set1_epi8(0x1F);
    uint32_t c0 = (uint32_t)_mm256_movemask_epi8(
        _mm256_cmpeq_epi8(_mm256_min_epu8(lo, t), lo));
    uint32_t c1 = (uint32_t)_mm256_movemask_epi8(
        _mm256_cmpeq_epi8(_mm256_min_epu8(hi, t), hi));
    out->ctrl = (uint64_t)c0 | ((uint64_t)c1 << 32);
    out->ws = mm_eq64(lo, hi, ' ') | mm_eq64(lo, hi, '\t')
            | mm_eq64(lo, hi, '\n') | mm_eq64(lo, hi, '\r');
}
#endif

static const uint8_t* json_struct_tbl() {
    static uint8_t tbl[256];
    static bool init = false;
    if (!init) {
        tbl['{'] = tbl['}'] = tbl['['] = tbl[']'] = tbl[':'] = tbl[','] = 1;
        init = true;
    }
    return tbl;
}

// simdjson's odd-length backslash-run resolver: returns the mask of
// positions preceded by an ODD number of consecutive backslashes (i.e.
// escaped characters), carrying run parity across 64-bit words so a
// trailing-backslash run crossing the boundary resolves exactly.
static inline uint64_t find_escaped(uint64_t bs_bits, uint64_t* prev_odd) {
    const uint64_t even_bits = 0x5555555555555555ULL;
    const uint64_t odd_bits = ~even_bits;
    uint64_t start_edges = bs_bits & ~(bs_bits << 1);
    // a run continuing from the previous word flips the parity of a
    // bit-0 start edge
    uint64_t even_start_mask = even_bits ^ *prev_odd;
    uint64_t even_starts = start_edges & even_start_mask;
    uint64_t odd_starts = start_edges & ~even_start_mask;
    uint64_t even_carries = bs_bits + even_starts;
    uint64_t odd_carries;
    bool ends_odd = __builtin_add_overflow(bs_bits, odd_starts, &odd_carries);
    odd_carries |= *prev_odd;
    *prev_odd = ends_odd ? 1 : 0;
    uint64_t even_carry_ends = even_carries & ~bs_bits;
    uint64_t odd_carry_ends = odd_carries & ~bs_bits;
    return (even_carry_ends & odd_bits) | (odd_carry_ends & even_bits);
}

// prefix XOR (carry-less multiply by ~0): bit i of the result is the XOR
// of bits [0, i] of x — the in-string parity transform.
#if defined(__x86_64__)
static const bool g_has_clmul = __builtin_cpu_supports("pclmul");

__attribute__((target("pclmul")))
static inline uint64_t prefix_xor_clmul(uint64_t x) {
    __m128i v = _mm_set_epi64x(0, (long long)x);
    __m128i ones = _mm_set1_epi8((char)0xFF);
    return (uint64_t)_mm_cvtsi128_si64(_mm_clmulepi64_si128(v, ones, 0));
}
#endif

static inline uint64_t prefix_xor(uint64_t x) {
#if defined(__x86_64__)
    if (g_has_clmul) return prefix_xor_clmul(x);
#endif
    x ^= x << 1;  x ^= x << 2;  x ^= x << 4;
    x ^= x << 8;  x ^= x << 16; x ^= x << 32;
    return x;
}

struct RowMasks {
    uint64_t in_string;   // inclusive: opening quote .. last content byte
    uint64_t escaped;
    uint64_t quote_real;  // unescaped quotes
    uint64_t structural;  // structural chars outside strings
    uint64_t structural_raw;  // structural chars, unmasked (CSV stage 2)
    uint64_t ctrl_in_str; // raw control bytes inside strings (strict JSON)
    uint64_t bs;          // raw escape-char mask (row "has escapes" flag)
    uint64_t ws_outside;  // JSON ws outside strings (the byte-ledger pool)
};

struct RowScanState {
    uint64_t prev_odd;       // backslash-run parity carry
    uint64_t prev_in_string; // 0 or ~0
};

static inline void scan_word(const uint8_t* p, int64_t nbytes, int esc_ch,
                             int quote_ch, int mode_json, int sep_ch,
                             RowScanState* st, RowMasks* out) {
    BlockMasks bm;
    uint8_t padded[64];
    const uint8_t* src = p;
#if defined(__x86_64__)
    if (g_has_avx512) {
        classify_block_avx512(src, nbytes, esc_ch, quote_ch, mode_json,
                              sep_ch, &bm);
    } else
#endif
    if (nbytes < 64) {
        memset(padded, 0, sizeof(padded));
        if (nbytes > 0) memcpy(padded, p, (size_t)nbytes);
        src = padded;
    }
#if defined(__x86_64__)
    if (g_has_avx512) {
        // masks already computed above
    } else if (g_has_avx2) {
        classify_block_avx2(src, esc_ch, quote_ch, mode_json, sep_ch, &bm);
    } else
#endif
    {
        static const uint8_t no_struct[256] = {};
        classify_block_scalar(src, esc_ch, quote_ch,
                              mode_json ? json_struct_tbl() : no_struct, &bm);
        if (!mode_json) {
            uint64_t stm = 0;
            for (int j = 0; j < 64; ++j)
                if ((int)src[j] == sep_ch) stm |= 1ULL << j;
            bm.structural = stm;
        }
    }
    uint64_t valid = nbytes >= 64 ? ~0ULL : ((1ULL << nbytes) - 1);
    bm.bs &= valid; bm.quote &= valid; bm.structural &= valid;
    bm.ctrl &= valid;
    uint64_t escaped = 0;
    if (esc_ch >= 0 && (bm.bs | st->prev_odd))
        escaped = find_escaped(bm.bs, &st->prev_odd);
    uint64_t q_real = bm.quote & ~escaped;
    uint64_t in_str = prefix_xor(q_real) ^ st->prev_in_string;
    st->prev_in_string = (uint64_t)((int64_t)in_str >> 63);
    out->in_string = in_str & valid;
    out->escaped = escaped & valid;
    out->quote_real = q_real;
    out->structural = bm.structural & ~in_str;
    out->structural_raw = bm.structural;
    out->ctrl_in_str = bm.ctrl & in_str;
    out->bs = bm.bs;
    out->ws_outside = bm.ws & ~in_str & valid;
}

// Row index: ordered positions of (structural outside strings) and real
// quotes.  Returns the count; flags get bit0 = raw control byte inside a
// string (strict JSON rejects), bit1 = unterminated string.
static int64_t build_row_index(const uint8_t* row, int64_t len, int esc_ch,
                               int quote_ch, int mode_json, int sep_ch,
                               uint32_t* pos_out, uint32_t* flags,
                               int64_t* ws_out = nullptr) {
    RowScanState st = {0, 0};
    RowMasks m;
    int64_t count = 0;
    int64_t ws = 0;
    uint32_t fl = 0;
    for (int64_t base = 0; base < len; base += 64) {
        scan_word(row + base, len - base, esc_ch, quote_ch, mode_json,
                  sep_ch, &st, &m);
        if (m.ctrl_in_str) fl |= 1;
        if (m.bs) fl |= 4;  // row carries escape chars somewhere
        ws += __builtin_popcountll(m.ws_outside);
        uint64_t bits = m.structural | m.quote_real;
        while (bits) {
            int j = __builtin_ctzll(bits);
            bits &= bits - 1;
            pos_out[count++] = (uint32_t)(base + j);
        }
    }
    if (st.prev_in_string) fl |= 2;
    *flags = fl;
    if (ws_out) *ws_out = ws;
    return count;
}

// ---------------------------------------------------------------------------
// Stage 2 (JSON): recursive-descent over the position index.
// ---------------------------------------------------------------------------

static inline bool jws_byte(uint8_t c) {
    return c == ' ' || c == '\t' || c == '\n' || c == '\r';
}

static inline bool jws_only(const uint8_t* d, int64_t a, int64_t b) {
    // token gaps are almost always 0 or 1 byte ("key": "v", ...)
    if (a >= b) return true;
    if (b - a == 1) return jws_byte(d[a]);
    for (int64_t i = a; i < b; ++i)
        if (!jws_byte(d[i])) return false;
    return true;
}

struct JWalk {
    const uint8_t* d;
    int64_t len;
    const uint32_t* pos;
    int64_t cnt;
};

// forward decl
static bool jwalk_value(const JWalk& w, int64_t from, int64_t* k,
                        int64_t* vo, int64_t* vl, int* kind, int depth,
                        bool has_bs, int64_t* acc);

// container := object | array, fully validated over the index.  Token
// bytes (string contents, scalar tokens, key contents) accumulate into
// *acc for the caller's per-row byte ledger — inter-token gaps are NOT
// scanned here; the ledger (entries + outside-string ws + tokens == row
// length) rejects any row with unaccounted garbage in one compare.
static bool jwalk_container(const JWalk& w, int64_t* k, int64_t* end_byte,
                            int depth, bool has_bs, int64_t* acc) {
    if (depth > 60 || *k >= w.cnt) return false;
    int64_t open = w.pos[*k];
    uint8_t oc = w.d[open];
    uint8_t close_c = oc == '{' ? '}' : ']';
    ++*k;
    if (*k >= w.cnt) return false;
    // empty container
    if (w.d[w.pos[*k]] == close_c) {
        *end_byte = w.pos[*k] + 1;
        ++*k;
        return true;
    }
    int64_t from = open + 1;
    for (;;) {
        if (oc == '{') {
            // key string
            if (*k + 1 >= w.cnt || w.d[w.pos[*k]] != '"'
                    || w.d[w.pos[*k + 1]] != '"')
                return false;
            *acc += w.pos[*k + 1] - w.pos[*k] - 1;
            *k += 2;
            if (*k >= w.cnt || w.d[w.pos[*k]] != ':') return false;
            from = w.pos[*k] + 1;
            ++*k;
        }
        int64_t vo, vl;
        int kind;
        if (!jwalk_value(w, from, k, &vo, &vl, &kind, depth + 1, has_bs,
                         acc))
            return false;
        if (*k >= w.cnt) return false;
        uint8_t tc = w.d[w.pos[*k]];
        if (tc != ',' && tc != close_c) return false;
        if (kind == 0) {
            // scalar token between from and the terminator
            int64_t a = from, b = w.pos[*k];
            while (a < b && jws_byte(w.d[a])) ++a;
            while (b > a && jws_byte(w.d[b - 1])) --b;
            if (b <= a || !json_scalar_valid(w.d + a, b - a)) return false;
            *acc += b - a;
        }
        from = w.pos[*k] + 1;
        bool done = tc == close_c;
        ++*k;
        if (done) { *end_byte = from; return true; }
    }
}

// value at `from`; on success *k consumed past the value's index entries
// (strings/containers) or left AT the terminator-to-be (scalar: kind 0,
// and vo/vl are NOT set — the caller owns token trimming).  kind: 0
// scalar, 1 string, 2 string-with-escapes, 3 container (vo/vl = raw
// span; for strings the span is the content BETWEEN the quotes).
static bool jwalk_value(const JWalk& w, int64_t from, int64_t* k,
                        int64_t* vo, int64_t* vl, int* kind, int depth,
                        bool has_bs, int64_t* acc) {
    (void)from;
    if (depth > 60) return false;
    if (*k >= w.cnt) { *kind = 0; return true; }  // scalar up to terminator
    int64_t e = w.pos[*k];
    uint8_t c = w.d[e];
    if (c == '"') {
        if (*k + 1 >= w.cnt || w.d[w.pos[*k + 1]] != '"') return false;
        int64_t close = w.pos[*k + 1];
        *vo = e + 1;
        *vl = close - e - 1;
        *acc += *vl;
        *kind = (has_bs && memchr(w.d + *vo, '\\', (size_t)*vl)) ? 2 : 1;
        *k += 2;
        return true;
    }
    if (c == '{' || c == '[') {
        int64_t end_byte;
        if (!jwalk_container(w, k, &end_byte, depth, has_bs, acc))
            return false;
        *vo = e;
        *vl = end_byte - e;
        *kind = 3;
        return true;
    }
    *kind = 0;  // scalar: terminator is the entry at *k (validated by caller)
    return true;
}

// JSON string unescape matching CPython json.loads (then utf-8 encode)
// byte semantics.  Returns decoded length, or -1 when the escape sequence
// is invalid / not UTF-8-encodable (lone surrogate) — callers route such
// rows to the per-row fallback.
static int64_t json_unescape(const uint8_t* s, int64_t len, uint8_t* dst) {
    int64_t o = 0;
    for (int64_t i = 0; i < len;) {
        uint8_t c = s[i];
        if (c != '\\') { dst[o++] = c; ++i; continue; }
        if (i + 1 >= len) return -1;
        uint8_t e = s[i + 1];
        i += 2;
        switch (e) {
            case '"': dst[o++] = '"'; break;
            case '\\': dst[o++] = '\\'; break;
            case '/': dst[o++] = '/'; break;
            case 'b': dst[o++] = '\b'; break;
            case 'f': dst[o++] = '\f'; break;
            case 'n': dst[o++] = '\n'; break;
            case 'r': dst[o++] = '\r'; break;
            case 't': dst[o++] = '\t'; break;
            case 'u': {
                if (i + 4 > len) return -1;
                uint32_t cp = 0;
                for (int h = 0; h < 4; ++h) {
                    uint8_t x = s[i + h];
                    cp <<= 4;
                    if (x >= '0' && x <= '9') cp |= (uint32_t)(x - '0');
                    else if (x >= 'a' && x <= 'f') cp |= (uint32_t)(x - 'a' + 10);
                    else if (x >= 'A' && x <= 'F') cp |= (uint32_t)(x - 'A' + 10);
                    else return -1;
                }
                i += 4;
                if (cp >= 0xD800 && cp <= 0xDBFF) {
                    // surrogate pair
                    if (i + 6 > len || s[i] != '\\' || s[i + 1] != 'u')
                        return -1;
                    uint32_t lo = 0;
                    for (int h = 0; h < 4; ++h) {
                        uint8_t x = s[i + 2 + h];
                        lo <<= 4;
                        if (x >= '0' && x <= '9') lo |= (uint32_t)(x - '0');
                        else if (x >= 'a' && x <= 'f')
                            lo |= (uint32_t)(x - 'a' + 10);
                        else if (x >= 'A' && x <= 'F')
                            lo |= (uint32_t)(x - 'A' + 10);
                        else return -1;
                    }
                    if (lo < 0xDC00 || lo > 0xDFFF) return -1;
                    i += 6;
                    cp = 0x10000 + ((cp - 0xD800) << 10) + (lo - 0xDC00);
                } else if (cp >= 0xDC00 && cp <= 0xDFFF) {
                    return -1;  // lone low surrogate: not UTF-8-encodable
                }
                if (cp < 0x80) {
                    dst[o++] = (uint8_t)cp;
                } else if (cp < 0x800) {
                    dst[o++] = (uint8_t)(0xC0 | (cp >> 6));
                    dst[o++] = (uint8_t)(0x80 | (cp & 0x3F));
                } else if (cp < 0x10000) {
                    dst[o++] = (uint8_t)(0xE0 | (cp >> 12));
                    dst[o++] = (uint8_t)(0x80 | ((cp >> 6) & 0x3F));
                    dst[o++] = (uint8_t)(0x80 | (cp & 0x3F));
                } else {
                    dst[o++] = (uint8_t)(0xF0 | (cp >> 18));
                    dst[o++] = (uint8_t)(0x80 | ((cp >> 12) & 0x3F));
                    dst[o++] = (uint8_t)(0x80 | ((cp >> 6) & 0x3F));
                    dst[o++] = (uint8_t)(0x80 | (cp & 0x3F));
                }
                break;
            }
            default: return -1;
        }
    }
    return o;
}

}  // namespace

extern "C" {

// Exported per-row structural bitmaps (the device twin's reference): each
// row gets W 64-bit words, bit j of word w = byte w*64+j of the row.
// mode 0 = JSON ({}[]:, structural, backslash escapes); mode 1 =
// delimiter (separator structural, no escapes, plain quote parity).
// Rows longer than W*64 bytes or out of arena bounds get zero masks.
void lct_struct_index(const uint8_t* arena, int64_t arena_len,
                      const int64_t* offsets, const int32_t* lengths,
                      int64_t n, int32_t mode, uint8_t sep, uint8_t quote,
                      int64_t W, uint64_t* out_string, uint64_t* out_struct,
                      uint64_t* out_escaped, uint64_t* out_quote) {
    int mode_json = mode == 0;
    int esc_ch = mode_json ? '\\' : -1;
    for (int64_t i = 0; i < n; ++i) {
        uint64_t* so = out_string + i * W;
        uint64_t* st = out_struct + i * W;
        uint64_t* eo = out_escaped + i * W;
        uint64_t* qo = out_quote + i * W;
        memset(so, 0, (size_t)W * 8);
        memset(st, 0, (size_t)W * 8);
        memset(eo, 0, (size_t)W * 8);
        memset(qo, 0, (size_t)W * 8);
        int64_t off = offsets[i];
        int64_t len = lengths[i] < 0 ? 0 : lengths[i];
        if (off < 0 || off + len > arena_len || len > W * 64) continue;
        RowScanState rs = {0, 0};
        RowMasks m;
        for (int64_t w = 0; w * 64 < len; ++w) {
            scan_word(arena + off + w * 64, len - w * 64, esc_ch, quote,
                      mode_json, sep, &rs, &m);
            so[w] = m.in_string;
            st[w] = m.structural;
            eo[w] = m.escaped;
            qo[w] = m.quote_real;
        }
    }
}

// Structural-index JSON object parse: F known keys extracted into
// field-major [F, n] span arrays; unknown keys appended to the CSR extras
// arrays; escaped string values decoded into side_buf (span offsets
// emitted as arena_len + side_offset).  row_status: 0 parsed, 1 fallback
// (malformed / index-unprovable — caller re-parses per row), 2 parsed
// with extras.  counts_out: [side_used, extra_used, n_fallback, n_drift].
// Returns 0, or -1 on invalid arguments.
int64_t lct_json_struct_parse(
        const uint8_t* arena, int64_t arena_len, const int64_t* offsets,
        const int32_t* lengths, int64_t n, const uint8_t* keys_blob,
        const int32_t* key_lens, int64_t F, int32_t* out_offs,
        int32_t* out_lens, uint8_t* row_status, uint8_t* side_buf,
        int64_t side_cap, int32_t* extra_rows, int32_t* extra_key_off,
        int32_t* extra_key_len, int32_t* extra_val_off,
        int32_t* extra_val_len, int64_t extra_cap, int64_t* counts_out) {
    if (F > 128 || n < 0) return -1;
    int64_t key_starts[128];
    // short keys (<= 8 bytes, the norm) compare as one masked u64 load
    uint64_t key_w64[128];
    uint64_t key_m64[128];
    {
        int64_t acc = 0;
        for (int64_t f = 0; f < F; ++f) {
            key_starts[f] = acc;
            acc += key_lens[f];
        }
        for (int64_t f = 0; f < F; ++f) {
            uint8_t pad[8] = {0, 0, 0, 0, 0, 0, 0, 0};
            int64_t kl = key_lens[f];
            if (kl <= 8) {
                memcpy(pad, keys_blob + key_starts[f], (size_t)kl);
                memcpy(&key_w64[f], pad, 8);
                key_m64[f] = kl == 8 ? ~0ULL : ((1ULL << (8 * kl)) - 1);
            } else {
                key_w64[f] = 0;
                key_m64[f] = 0;  // long key: memcmp path
            }
        }
    }
    for (int64_t f = 0; f < F; ++f)
        for (int64_t i = 0; i < n; ++i) out_lens[f * n + i] = -1;

    int64_t max_len = 0;
    for (int64_t i = 0; i < n; ++i)
        if (lengths[i] > max_len) max_len = lengths[i];
    uint32_t* posbuf = max_len
        ? (uint32_t*)malloc((size_t)max_len * sizeof(uint32_t)) : nullptr;
    if (max_len && !posbuf) return -1;

    int64_t side_used = 0, extra_used = 0, n_fallback = 0, n_drift = 0;
    // schema-order hint: stable-schema rows repeat key order, so try the
    // slot that matched at this member position last time first
    int32_t order_hint[128];
    for (int64_t f = 0; f < F; ++f) order_hint[f] = (int32_t)f;

    // Template replay (the steady-state fast path): machine-generated log
    // streams repeat one member layout for thousands of rows.  After a
    // generic row parses clean (no drift, no escapes, flat string/scalar
    // values), record (kind, slot) per member; the next row with the same
    // entry count replays that layout with direct char checks, masked-u64
    // key compares, scalar validation and the byte ledger — no recursive
    // walk.  ANY mismatch falls back to the generic walk for that row.
    int tpl_valid = 0;
    int64_t tpl_cnt = 0;
    int tpl_nm = 0;
    int8_t tpl_kind[64];
    int16_t tpl_slot[64];

    for (int64_t i = 0; i < n; ++i) {
        row_status[i] = 0;
        int64_t off = offsets[i];
        int64_t len = lengths[i] < 0 ? 0 : lengths[i];
        if (off < 0 || off + len > arena_len) {
            row_status[i] = 1; ++n_fallback; continue;
        }
        const uint8_t* d = arena + off;
        uint32_t flags = 0;
        int64_t row_ws = 0;
        int64_t cnt = build_row_index(d, len, '\\', '"', 1, 0, posbuf,
                                      &flags, &row_ws);
        int64_t side_mark = side_used, extra_mark = extra_used;
        bool bad = (flags & 3) != 0;     // ctrl-in-string / unterminated
        bool row_has_bs = (flags & 4) != 0;
        bool drift = false;
        if (!bad && tpl_valid && !row_has_bs && cnt == tpl_cnt
                && d[posbuf[0]] == '{') {
            bool okr = true;
            int64_t k2 = 1;
            int64_t acc2 = 0;
            for (int m = 0; m < tpl_nm; ++m) {
                if (d[posbuf[k2]] != '"' || d[posbuf[k2 + 1]] != '"') {
                    okr = false; break;
                }
                int64_t ko = posbuf[k2] + 1;
                int64_t kl2 = posbuf[k2 + 1] - ko;
                int64_t slot = tpl_slot[m];
                if (key_lens[slot] != kl2) { okr = false; break; }
                if (kl2 <= 8 && off + ko + 8 <= arena_len && key_m64[slot]) {
                    uint64_t rw;
                    memcpy(&rw, d + ko, 8);
                    if ((rw & key_m64[slot]) != key_w64[slot]) {
                        okr = false; break;
                    }
                } else if (memcmp(keys_blob + key_starts[slot], d + ko,
                                  (size_t)kl2) != 0) {
                    okr = false; break;
                }
                if (d[posbuf[k2 + 2]] != ':') { okr = false; break; }
                int64_t vo2, vl2, term;
                if (tpl_kind[m] == 1) {
                    if (d[posbuf[k2 + 3]] != '"'
                            || d[posbuf[k2 + 4]] != '"') {
                        okr = false; break;
                    }
                    vo2 = posbuf[k2 + 3] + 1;
                    vl2 = posbuf[k2 + 4] - vo2;
                    term = k2 + 5;
                    k2 += 6;
                } else {
                    int64_t a = posbuf[k2 + 2] + 1;
                    term = k2 + 3;
                    int64_t b = posbuf[term];
                    while (a < b && jws_byte(d[a])) ++a;
                    while (b > a && jws_byte(d[b - 1])) --b;
                    if (b <= a || !json_scalar_valid(d + a, b - a)) {
                        okr = false; break;
                    }
                    vo2 = a; vl2 = b - a;
                    k2 += 4;
                }
                uint8_t tc = d[posbuf[term]];
                if (tc != (m == tpl_nm - 1 ? '}' : ',')) {
                    okr = false; break;
                }
                acc2 += kl2 + vl2;
                out_offs[slot * n + i] = (int32_t)(off + vo2);
                out_lens[slot * n + i] = (int32_t)vl2;
            }
            if (okr && k2 == cnt && cnt + row_ws + acc2 == len) {
                row_status[i] = 0;
                continue;           // replay complete: next row
            }
            // replay rejected: reset partial emits, run the generic walk
            for (int64_t f = 0; f < F; ++f) out_lens[f * n + i] = -1;
        }
        JWalk w = {d, len, posbuf, cnt};
        int64_t k = 0;
        int64_t member_idx = 0;
        int tpl_build_nm = 0;
        bool tpl_build_ok = true;
        // byte ledger: every row byte must be an index entry, a token
        // byte, or outside-string whitespace — one compare at the end
        // replaces every inter-token whitespace scan
        int64_t acc = 0;
        if (!bad && (cnt == 0 || d[posbuf[0]] != '{'))
            bad = true;
        if (!bad) {
            k = 1;
            // empty object
            if (k < cnt && d[posbuf[k]] == '}') {
                k = 2;
            } else {
                for (;;) {
                    // key
                    if (k + 1 >= cnt || d[posbuf[k]] != '"'
                            || d[posbuf[k + 1]] != '"') {
                        bad = true; break;
                    }
                    int64_t ko = posbuf[k] + 1;
                    int64_t kl = posbuf[k + 1] - ko;
                    if (row_has_bs && memchr(d + ko, '\\', (size_t)kl)) {
                        // escaped key: index-unprovable → counted fallback
                        bad = true; break;
                    }
                    acc += kl;
                    k += 2;
                    if (k >= cnt || d[posbuf[k]] != ':') {
                        bad = true; break;
                    }
                    int64_t from = posbuf[k] + 1;
                    ++k;
                    int64_t vo = 0, vl = 0;
                    int kind = 0;
                    if (!jwalk_value(w, from, &k, &vo, &vl, &kind, 0,
                                     row_has_bs, &acc)) {
                        bad = true; break;
                    }
                    if (k >= cnt) { bad = true; break; }
                    uint8_t tc = d[posbuf[k]];
                    if (tc != ',' && tc != '}') { bad = true; break; }
                    if (kind == 0) {
                        int64_t a = from, b = posbuf[k];
                        while (a < b && jws_byte(d[a])) ++a;
                        while (b > a && jws_byte(d[b - 1])) --b;
                        if (b <= a || !json_scalar_valid(d + a, b - a)) {
                            bad = true; break;
                        }
                        vo = a; vl = b - a;
                        acc += vl;
                    }
                    // emit value span (decode escapes into the side arena)
                    int64_t evo = off + vo, evl = vl;
                    if (kind == 2) {
                        if (side_used + vl > side_cap) { bad = true; break; }
                        int64_t dl = json_unescape(d + vo, vl,
                                                   side_buf + side_used);
                        if (dl < 0) { bad = true; break; }
                        evo = arena_len + side_used;
                        evl = dl;
                        side_used += dl;
                    }
                    // schema match (order-hint first, then linear);
                    // the row key loads as a masked u64 when the 8-byte
                    // read stays inside the arena
                    int64_t slot = -1;
                    uint64_t row_w64 = 0;
                    bool fast_key = kl <= 8 && off + ko + 8 <= arena_len;
                    if (fast_key) memcpy(&row_w64, d + ko, 8);
                    if (member_idx < F) {
                        int32_t h = order_hint[member_idx];
                        if (key_lens[h] == kl
                                && (fast_key && key_m64[h]
                                    ? (row_w64 & key_m64[h]) == key_w64[h]
                                    : memcmp(keys_blob + key_starts[h],
                                             d + ko, (size_t)kl) == 0))
                            slot = h;
                    }
                    if (slot < 0) {
                        for (int64_t f = 0; f < F; ++f) {
                            if (key_lens[f] != kl) continue;
                            if (fast_key && key_m64[f]
                                    ? (row_w64 & key_m64[f]) != key_w64[f]
                                    : memcmp(keys_blob + key_starts[f],
                                             d + ko, (size_t)kl) != 0)
                                continue;
                            slot = f;
                            if (member_idx < F)
                                order_hint[member_idx] = (int32_t)f;
                            break;
                        }
                    }
                    if (slot >= 0) {
                        out_offs[slot * n + i] = (int32_t)evo;
                        out_lens[slot * n + i] = (int32_t)evl;
                        if (tpl_build_ok && member_idx < 64
                                && (kind == 0 || kind == 1)) {
                            tpl_kind[member_idx] = (int8_t)kind;
                            tpl_slot[member_idx] = (int16_t)slot;
                            tpl_build_nm = (int)member_idx + 1;
                        } else {
                            tpl_build_ok = false;
                        }
                    } else {
                        tpl_build_ok = false;
                        if (extra_used >= extra_cap) { bad = true; break; }
                        extra_rows[extra_used] = (int32_t)i;
                        extra_key_off[extra_used] = (int32_t)(off + ko);
                        extra_key_len[extra_used] = (int32_t)kl;
                        extra_val_off[extra_used] = (int32_t)evo;
                        extra_val_len[extra_used] = (int32_t)evl;
                        ++extra_used;
                        drift = true;
                    }
                    ++member_idx;
                    bool done = tc == '}';
                    ++k;
                    if (done) break;
                }
            }
            // ledger + no trailing index entries after the closing brace
            if (!bad && (k != cnt || cnt + row_ws + acc != len))
                bad = true;
        }
        if (bad) {
            row_status[i] = 1;
            ++n_fallback;
            side_used = side_mark;
            extra_used = extra_mark;
            for (int64_t f = 0; f < F; ++f) out_lens[f * n + i] = -1;
        } else if (drift) {
            row_status[i] = 2;
            ++n_drift;
        } else if (tpl_build_ok && !row_has_bs && tpl_build_nm > 0
                   && member_idx == tpl_build_nm) {
            tpl_valid = 1;
            tpl_cnt = cnt;
            tpl_nm = tpl_build_nm;
        }
    }
    free(posbuf);
    counts_out[0] = side_used;
    counts_out[1] = extra_used;
    counts_out[2] = n_fallback;
    counts_out[3] = n_drift;
    return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Stage 2 (quote-mode delimiter): position-stream walk reproducing the
// DelimiterModeFsmParser state table (core/parser/DelimiterModeFsmParser.h)
// field-for-field:
//   * a quote OPENS a quoted section only as the field's first byte;
//   * inside quotes, a doubled quote escapes to one literal quote and
//     separators are content;
//   * after the closing quote, bytes up to the next separator append
//     literally (including quotes);
//   * an unterminated quote consumes the rest of the row as content.
// Fields needing byte rewrites (doubled quotes, quoted-head + literal
// tail) are materialised in side_buf; clean fields stay pure spans.
// ---------------------------------------------------------------------------

namespace {

struct CsvEmit {
    int64_t off;   // arena offset, or arena_len + side offset
    int64_t len;
};

// Decode ONE field starting at `start` (absolute row positions) given the
// position stream (quotes + raw separators, ordered).  Advances *k past
// the field's entries; returns the exclusive end (the separator position,
// or row len).  If the field needs a rewrite, copies decoded bytes into
// side_buf at *side_used (caller checks capacity beforehand: decoded
// length never exceeds the raw field length).
static int64_t csv_field(const uint8_t* d, int64_t len, const uint32_t* pos,
                         int64_t cnt, int64_t* k, int64_t start,
                         uint8_t quote, int64_t arena_off, int64_t arena_len,
                         uint8_t* side_buf, int64_t* side_used,
                         int64_t side_cap, CsvEmit* out) {
    (void)side_cap;  // capacity is pre-checked per row by the caller
    while (*k < cnt && (int64_t)pos[*k] < start) ++*k;
    // unquoted field: up to the next raw separator; quotes are literal
    if (start >= len || d[start] != quote) {
        int64_t kk = *k;
        int64_t end = len;
        while (kk < cnt) {
            if (d[pos[kk]] != quote) { end = pos[kk]; break; }
            ++kk;
        }
        // consume entries inside the field plus the separator
        while (*k < cnt && (int64_t)pos[*k] < end) ++*k;
        out->off = arena_off + start;
        out->len = end - start;
        return end;
    }
    // quoted field: scan quote entries for the close, collapsing doubles
    int64_t i = start + 1;      // content cursor (raw)
    ++*k;                        // past the opening quote
    bool doubled = false;
    int64_t close = -1;
    while (*k < cnt) {
        int64_t p = pos[*k];
        if (d[p] != quote) { ++*k; continue; }  // separator inside quotes
        if (*k + 1 < cnt && (int64_t)pos[*k + 1] == p + 1
                && d[pos[*k + 1]] == quote) {
            doubled = true;
            *k += 2;
            continue;
        }
        close = p;
        ++*k;
        break;
    }
    if (close < 0) {
        // unterminated: rest of row is content (with doubles collapsed)
        if (!doubled) {
            out->off = arena_off + i;
            out->len = len - i;
            return len;
        }
        int64_t so = *side_used;
        int64_t o = so;
        // capacity is guaranteed by the caller's per-row `len` pre-check
        for (int64_t j = i; j < len; ++j) {
            side_buf[o++] = d[j];
            if (d[j] == quote && j + 1 < len && d[j + 1] == quote) ++j;
        }
        out->off = arena_len + so;
        out->len = o - so;
        *side_used = o;
        return len;
    }
    // field end: next raw separator after the close
    int64_t end = len;
    while (*k < cnt) {
        if (d[pos[*k]] != quote) { end = pos[*k]; break; }
        ++*k;
    }
    while (*k < cnt && (int64_t)pos[*k] < end) ++*k;
    bool tail = end > close + 1;
    if (!doubled && !tail) {
        out->off = arena_off + i;
        out->len = close - i;
        return end;
    }
    int64_t so = *side_used;
    int64_t o = so;
    for (int64_t j = i; j < close; ++j) {
        side_buf[o++] = d[j];
        if (d[j] == quote && j + 1 < close && d[j + 1] == quote) ++j;
    }
    for (int64_t j = close + 1; j < end; ++j) side_buf[o++] = d[j];
    out->off = arena_len + so;
    out->len = o - so;
    *side_used = o;
    return end;
}

}  // namespace

extern "C" {

// Quote-mode delimiter parse from the structural index.  Emits the first
// F-1 fields as spans and joins fields [F-1, nfields) with the separator
// (the reference's "last key takes the rest" rule applied to PROCESSED
// fields, matching the host FSM + join path byte-for-byte).  Output spans
// are event-major [n, F]; len -1 = absent.  nfields_out[i] = total fields
// the row splits into.  counts_out: [side_used, n_rewrites].
// Returns 0, or -1 on invalid arguments / side buffer overflow.
int64_t lct_delim_struct_parse(
        const uint8_t* arena, int64_t arena_len, const int64_t* offsets,
        const int32_t* lengths, int64_t n, uint8_t sep, uint8_t quote,
        int64_t F, int32_t* out_offs, int32_t* out_lens,
        int32_t* nfields_out, uint8_t* side_buf, int64_t side_cap,
        int64_t* counts_out) {
    if (F <= 0 || n < 0) return -1;
    int64_t max_len = 0;
    for (int64_t i = 0; i < n; ++i)
        if (lengths[i] > max_len) max_len = lengths[i];
    uint32_t* posbuf = max_len
        ? (uint32_t*)malloc((size_t)max_len * sizeof(uint32_t)) : nullptr;
    if (max_len && !posbuf) return -1;
    int64_t side_used = 0, rewrites = 0;
    for (int64_t i = 0; i < n; ++i) {
        for (int64_t f = 0; f < F; ++f) out_lens[i * F + f] = -1;
        nfields_out[i] = 0;
        int64_t off = offsets[i];
        int64_t len = lengths[i] < 0 ? 0 : lengths[i];
        if (off < 0 || off + len > arena_len) continue;
        // every decoded byte lands in side_buf at most once and decoding
        // never expands, so one row needs at most `len` bytes of side
        if (side_used + len > side_cap) { free(posbuf); return -1; }
        const uint8_t* d = arena + off;
        // raw position stream (quotes + ALL separators): the FSM walk
        // applies quote semantics itself, so the parity in-string mask —
        // which a literal mid-field quote can desynchronise — is never
        // trusted for field boundaries
        int64_t cnt = 0;
        {
            RowScanState rs = {0, 0};
            RowMasks m;
            for (int64_t base = 0; base < len; base += 64) {
                scan_word(d + base, len - base, -1, quote, 0, sep, &rs, &m);
                uint64_t bits = m.quote_real | m.structural_raw;
                while (bits) {
                    int j = __builtin_ctzll(bits);
                    bits &= bits - 1;
                    posbuf[cnt++] = (uint32_t)(base + j);
                }
            }
        }
        int64_t k = 0, start = 0, fidx = 0;
        int64_t side_mark = side_used;
        bool joining = false;       // fields >= F merge into the last slot
        int64_t join_start = 0;     // side offset of the merged value
        for (;;) {
            if (joining) side_buf[side_used++] = sep;
            CsvEmit e;
            int64_t end = csv_field(d, len, posbuf, cnt, &k, start, quote,
                                    off, arena_len, side_buf, &side_used,
                                    side_cap, &e);
            if (joining) {
                if (e.off < arena_len) {  // pure span: append bytes
                    memcpy(side_buf + side_used, arena + e.off,
                           (size_t)e.len);
                    side_used += e.len;
                }
                // side spans were decoded in place at the join tail
                out_lens[i * F + (F - 1)] =
                    (int32_t)(side_used - join_start);
            } else if (fidx < F) {
                out_offs[i * F + fidx] = (int32_t)e.off;
                out_lens[i * F + fidx] = (int32_t)e.len;
            }
            ++fidx;
            if (end >= len) break;
            start = end + 1;
            if (!joining && fidx == F) {
                // more fields follow: convert the last slot to join mode
                int64_t slot = i * F + (F - 1);
                if (out_offs[slot] >= arena_len) {
                    join_start = out_offs[slot] - arena_len;
                } else {
                    join_start = side_used;
                    memcpy(side_buf + side_used, arena + out_offs[slot],
                           (size_t)out_lens[slot]);
                    side_used += out_lens[slot];
                    out_offs[slot] = (int32_t)(arena_len + join_start);
                }
                joining = true;
            }
        }
        nfields_out[i] = (int32_t)fidx;
        if (side_used != side_mark) ++rewrites;
    }
    free(posbuf);
    counts_out[0] = side_used;
    counts_out[1] = rewrites;
    return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// loongagg: hashed segment-reduce over columnar metric batches.
//
// One call folds a whole batch: every row's segment identity is
// (window slot, K key spans) — hashed span-wise (SSE4.2 CRC32 lanes when
// the CPU has them, 8-byte-wide FNV-1a otherwise), resolved through an
// open-addressing table with full byte verification on hash hits, so
// collisions can regroup nothing.  Values parse from their text spans
// under a strtod-subset grammar shared verbatim with the numpy twin
// (ops/kernels/segment_reduce.py), and the per-group aggregates
// (sum/count/min/max/last + the metrics.py-shaped log2-bucket histogram)
// accumulate in f64 IN ROW ORDER — the property that makes the numpy twin
// bit-identical and the per-event dict path value-identical.
// Group ids are assigned in first-seen row order (deterministic across
// substrates); rep_row[g] lets the caller read back the group's slot and
// key spans without any per-row host work.
// ---------------------------------------------------------------------------

#include <cmath>

namespace {

// strtod-subset grammar shared with the numpy twin: optional sign, then
// decimal digits[.digits] | .digits with optional exponent, or
// inf/infinity/nan (case-insensitive).  Hex floats, underscores and
// locale forms are invalid on EVERY substrate — the grammar, not the
// host libc, defines validity.
static bool agg_ci_word(const uint8_t* s, int64_t len, const char* w) {
    for (int64_t i = 0; i < len; ++i) {
        if (w[i] == 0) return false;
        uint8_t c = s[i];
        if (c >= 'A' && c <= 'Z') c = (uint8_t)(c + 32);
        if (c != (uint8_t)w[i]) return false;
    }
    return w[len] == 0;
}

static bool agg_value_grammar(const uint8_t* s, int64_t len) {
    int64_t i = 0;
    if (i < len && (s[i] == '+' || s[i] == '-')) ++i;
    if (i >= len) return false;
    // inf folds fine (sum->inf, min/max compare); NaN would make min/max
    // accumulation order-visible across substrates, so it is INVALID by
    // grammar — rejected rows take the counted invalid path instead
    if (agg_ci_word(s + i, len - i, "inf") ||
        agg_ci_word(s + i, len - i, "infinity"))
        return true;
    bool digits = false;
    while (i < len && s[i] >= '0' && s[i] <= '9') { ++i; digits = true; }
    if (i < len && s[i] == '.') {
        ++i;
        while (i < len && s[i] >= '0' && s[i] <= '9') { ++i; digits = true; }
    }
    if (!digits) return false;
    if (i < len && (s[i] == 'e' || s[i] == 'E')) {
        ++i;
        if (i < len && (s[i] == '+' || s[i] == '-')) ++i;
        bool edigits = false;
        while (i < len && s[i] >= '0' && s[i] <= '9') { ++i; edigits = true; }
        if (!edigits) return false;
    }
    return i == len;
}

// Clinger fast path: mantissa <= 2^53 times an EXACT power of ten
// (|e| <= 22) is one IEEE multiply/divide of exact operands — correctly
// rounded, i.e. bit-identical to strtod and Python float().  Typical
// metric values ("2.5", "17", "0.125") all land here; anything longer or
// wider falls through to strtod.
static const double kAggPow10[23] = {
    1e0,  1e1,  1e2,  1e3,  1e4,  1e5,  1e6,  1e7,  1e8,  1e9,  1e10,
    1e11, 1e12, 1e13, 1e14, 1e15, 1e16, 1e17, 1e18, 1e19, 1e20, 1e21,
    1e22};

static bool agg_parse_fast(const uint8_t* s, int64_t len, double* out) {
    int64_t i = 0;
    bool neg = false;
    if (s[i] == '+' || s[i] == '-') {
        neg = (s[i] == '-');
        ++i;
    }
    uint64_t mant = 0;
    int digits = 0;
    int frac = 0;
    bool dot = false;
    for (; i < len; ++i) {
        uint8_t c = s[i];
        if (c >= '0' && c <= '9') {
            if (++digits > 17) return false;  // may exceed 2^53: slow path
            mant = mant * 10 + (c - '0');
            if (dot) ++frac;
        } else if (c == '.' && !dot) {
            dot = true;
        } else {
            return false;  // exponent / inf spellings: slow path
        }
    }
    if (digits == 0) return false;
    int e = -frac;
    if (e < -22 || e > 22 || mant > (1ULL << 53)) return false;
    double v = (double)mant;
    v = (e < 0) ? v / kAggPow10[-e] : v * kAggPow10[e];
    *out = neg ? -v : v;
    return true;
}

static bool agg_parse_value(const uint8_t* s, int32_t vlen, double* out) {
    int64_t len = vlen;
    while (len > 0 && (*s == ' ' || *s == '\t')) { ++s; --len; }
    while (len > 0 && (s[len - 1] == ' ' || s[len - 1] == '\t')) --len;
    if (len <= 0) return false;
    if (agg_parse_fast(s, len, out)) return true;
    if (!agg_value_grammar(s, len)) return false;
    char stack_buf[64];
    char* buf = stack_buf;
    char* heap = nullptr;
    if (len >= 63) {
        heap = (char*)malloc((size_t)len + 1);
        if (!heap) return false;
        buf = heap;
    }
    memcpy(buf, s, (size_t)len);
    buf[len] = 0;
    char* end = nullptr;
    double v = strtod(buf, &end);
    bool ok = (end == buf + len);
    free(heap);
    if (!ok) return false;
    *out = v;
    return true;
}

// The metrics.py Histogram bucket shape (log2 boundaries): v <= base (and
// NaN, and negatives) land in bucket 0, otherwise ceil(log2(v/base))
// clamped to the last slot; +inf goes to the last (+Inf) slot directly —
// frexp(inf) is substrate-dependent, the explicit case is not.
static int64_t agg_hist_bucket(double v, double base, int64_t nb) {
    if (std::isinf(v) && v > 0.0) return nb - 1;
    if (!(v > base)) return 0;
    int e = 0;
    double m = std::frexp(v / base, &e);
    int64_t idx = (m == 0.5) ? (int64_t)e - 1 : (int64_t)e;
    if (idx < 0) idx = 0;
    if (idx > nb - 1) idx = nb - 1;
    return idx;
}

static uint64_t agg_span_hash_fnv(uint64_t h, const uint8_t* p, int64_t len) {
    // 8-byte-wide FNV-1a mix; identity across substrates is irrelevant
    // (collisions byte-verify), only distribution matters
    while (len >= 8) {
        uint64_t w;
        memcpy(&w, p, 8);
        h = (h ^ w) * 0x100000001b3ULL;
        p += 8;
        len -= 8;
    }
    if (len > 0) {
        uint64_t w = 0;
        memcpy(&w, p, (size_t)len);
        h = (h ^ (w | ((uint64_t)len << 56))) * 0x100000001b3ULL;
    }
    return h;
}

#if defined(__x86_64__)
static const bool g_has_sse42 = __builtin_cpu_supports("sse4.2");

// Two independent CRC32C lanes, 16 bytes per iteration (crc32q has a
// 3-cycle latency; two chains hide it), folded with a golden-ratio mix.
__attribute__((target("sse4.2"))) static uint64_t agg_span_hash_crc(
        uint64_t h, const uint8_t* p, int64_t len) {
    uint64_t c0 = (uint32_t)h;
    uint64_t c1 = (uint32_t)(h >> 32) ^ 0x9e3779b9u;
    while (len >= 16) {
        uint64_t w0, w1;
        memcpy(&w0, p, 8);
        memcpy(&w1, p + 8, 8);
        c0 = _mm_crc32_u64(c0, w0);
        c1 = _mm_crc32_u64(c1, w1);
        p += 16;
        len -= 16;
    }
    while (len >= 8) {
        uint64_t w;
        memcpy(&w, p, 8);
        c0 = _mm_crc32_u64(c0, w);
        p += 8;
        len -= 8;
    }
    if (len > 0) {
        uint64_t w = 0;
        memcpy(&w, p, (size_t)len);
        c1 = _mm_crc32_u64(c1, w | ((uint64_t)len << 56));
    }
    return ((c1 << 32) | c0) * 0x9E3779B97F4A7C15ULL;
}
#endif

static inline uint64_t agg_span_hash(uint64_t h, const uint8_t* p,
                                     int64_t len) {
#if defined(__x86_64__)
    if (g_has_sse42) return agg_span_hash_crc(h, p, len);
#endif
    return agg_span_hash_fnv(h, p, len);
}

static bool agg_rows_equal(const uint8_t* arena, const int64_t* slots,
                           const int64_t* key_offs, const int32_t* key_lens,
                           int64_t K, int64_t a, int64_t b) {
    if (slots[a] != slots[b]) return false;
    for (int64_t k = 0; k < K; ++k) {
        int32_t la = key_lens[a * K + k];
        int32_t lb = key_lens[b * K + k];
        if (la != lb) return false;
        if (la > 0 && memcmp(arena + key_offs[a * K + k],
                             arena + key_offs[b * K + k],
                             (size_t)la) != 0)
            return false;
    }
    return true;
}

}  // namespace

extern "C" {

// Returns n_groups (>= 0), -1 when cap was too small (caller grows cap and
// retries; n_groups <= n so cap = n can never fail), -2 on OOM.
// group_id[i]: the row's group in first-seen order, or -1 for rows whose
// value span fails the shared grammar (the caller's counted invalid path).
// out_hist is [cap, n_hist] row-major, metrics.py log2 bucket shape.
int64_t lct_group_reduce(
        const uint8_t* arena, int64_t arena_len,
        const int64_t* slots,
        const int64_t* key_offs, const int32_t* key_lens,
        const int64_t* val_offs, const int32_t* val_lens,
        int64_t n, int64_t K,
        double hist_base, int64_t n_hist,
        int32_t* group_id, int32_t* rep_row,
        double* out_sum, int64_t* out_cnt,
        double* out_min, double* out_max, double* out_last,
        int64_t* out_hist, int64_t cap) {
    (void)arena_len;
    if (n <= 0) return 0;
    int64_t tsize = 16;
    while (tsize < 2 * n) tsize <<= 1;
    int32_t* table = (int32_t*)malloc((size_t)tsize * sizeof(int32_t));
    uint64_t* thash = (uint64_t*)malloc((size_t)tsize * sizeof(uint64_t));
    if (!table || !thash) {
        free(table);
        free(thash);
        return -2;
    }
    memset(table, 0xFF, (size_t)tsize * sizeof(int32_t));
    const uint64_t mask = (uint64_t)tsize - 1;
    int64_t n_groups = 0;
    int64_t rc = 0;
    for (int64_t i = 0; i < n; ++i) {
        double v = 0.0;
        int32_t vl = val_lens[i];
        if (vl < 0 || !agg_parse_value(arena + val_offs[i], vl, &v)) {
            group_id[i] = -1;
            continue;
        }
        uint64_t h = 0xcbf29ce484222325ULL ^
                     ((uint64_t)slots[i] * 0x9E3779B97F4A7C15ULL);
        h ^= h >> 29;
        for (int64_t k = 0; k < K; ++k) {
            int32_t kl = key_lens[i * K + k];
            // the length term keeps absent (-1) distinct from empty, and
            // ("ab","") distinct from ("a","b")
            h = (h ^ ((uint64_t)(int64_t)kl + 2)) * 0x100000001b3ULL;
            if (kl > 0)
                h = agg_span_hash(h, arena + key_offs[i * K + k], kl);
        }
        // avalanche before masking: both span hashes leave LOW bits
        // under-mixed (CRC lanes put one lane's bits only in the high
        // half; FNV multiplies carry low bits upward only), and keys
        // sharing an 8-byte prefix would otherwise cluster into a
        // handful of buckets — O(G^2) probing at high cardinality
        h ^= h >> 33;
        h *= 0xff51afd7ed558ccdULL;
        h ^= h >> 29;
        uint64_t pos = h & mask;
        int64_t g = -1;
        for (;;) {
            int32_t t = table[pos];
            if (t < 0) {
                if (n_groups >= cap) {
                    rc = -1;
                    goto done;
                }
                g = n_groups++;
                table[pos] = (int32_t)g;
                thash[pos] = h;
                rep_row[g] = (int32_t)i;
                out_sum[g] = 0.0;
                out_cnt[g] = 0;
                out_min[g] = v;
                out_max[g] = v;
                memset(out_hist + g * n_hist, 0,
                       (size_t)n_hist * sizeof(int64_t));
                break;
            }
            if (thash[pos] == h &&
                agg_rows_equal(arena, slots, key_offs, key_lens, K,
                               (int64_t)rep_row[t], i)) {
                g = t;
                break;
            }
            pos = (pos + 1) & mask;
        }
        group_id[i] = (int32_t)g;
        out_sum[g] += v;
        out_cnt[g] += 1;
        if (v < out_min[g]) out_min[g] = v;
        if (v > out_max[g]) out_max[g] = v;
        out_last[g] = v;
        out_hist[g * n_hist + agg_hist_bucket(v, hist_base, n_hist)] += 1;
    }
done:
    free(table);
    free(thash);
    return rc < 0 ? rc : n_groups;
}

}  // extern "C"
