// One rendered chunk's share of a tabix index (variantcalling_tpu/io/tabix.py,
// chunk_index_facts): the native scan's CHROM codes, POS and len(REF) laid on
// the lines of the rendered body, in one pass.
//
// What build_tabix_index (the second pass, and the tests' oracle) works out a
// record at a time in Python comes out here as arrays: where each line ends,
// the runs of neighbouring records of one contig and one UCSC bin, and for
// every 16 kb window the chunk touches the first record over it. Offsets are
// from the chunk's first byte; the ordered half (StreamedIndex) knows where
// that byte lies in the file.
//
// Single-threaded on purpose: the streaming executor's workers are the
// parallelism, and ctypes releases the interpreter for the whole call.

#include <cstdint>
#include <cstring>

namespace {

// tabix.reg2bin: the smallest bin that holds [beg, end)
inline int64_t reg2bin(int64_t beg, int64_t end) {
    --end;
    if (beg >> 14 == end >> 14) return 4681 + (beg >> 14);
    if (beg >> 17 == end >> 17) return 585 + (beg >> 17);
    if (beg >> 20 == end >> 20) return 73 + (beg >> 20);
    if (beg >> 23 == end >> 23) return 9 + (beg >> 23);
    if (beg >> 26 == end >> 26) return 1 + (beg >> 26);
    return 0;
}

}  // namespace

extern "C" {

// counts[0..2] = contigs, runs, windows written. Returns 0, or why the chunk
// cannot vouch for the index: -1 the body is not one line a record, -2 a
// record before the one ahead of it in its contig or starting before 0,
// -3 more windows than `win_cap`.
int64_t vctpu_tabix_chunk_facts(
    const uint8_t* body, int64_t body_len, int64_t n,
    const int32_t* codes, const int64_t* pos, const int32_t* ref_len,
    int64_t* ends,          // (n) where each record's line ends
    int64_t* contig_first,  // (n) first record of each run of one contig
    int64_t* run_first,     // (n) first record of each run of one contig and bin
    int64_t* run_contig,    // (n) ... its contig run's number
    int64_t* run_bin,       // (n)
    int64_t* win_contig,    // (win_cap) a window's contig run's number
    int64_t* win,           // (win_cap) the window
    int64_t* win_start,     // (win_cap) where the first record over it starts
    int64_t win_cap, int64_t* counts) {
    int64_t off = 0, n_contig = 0, n_run = 0, n_win = 0;
    int64_t prev_beg = 0, prev_bin = -1, win_max = -1;
    for (int64_t i = 0; i < n; ++i) {
        const int64_t start = off;
        const void* nl = off < body_len ? std::memchr(body + off, '\n', body_len - off) : nullptr;
        if (nl == nullptr) return -1;
        ends[i] = off = static_cast<const uint8_t*>(nl) - body + 1;
        const int64_t beg = pos[i] - 1;  // VCF is 1-based
        const int64_t end = beg + (ref_len[i] > 1 ? ref_len[i] : 1);
        const bool new_contig = i == 0 || codes[i] != codes[i - 1];
        if (beg < 0 || (!new_contig && beg < prev_beg)) return -2;
        prev_beg = beg;
        if (new_contig) {
            contig_first[n_contig++] = i;
            win_max = -1;
        }
        const int64_t bin = reg2bin(beg, end);
        if (new_contig || bin != prev_bin) {
            run_first[n_run] = i;
            run_contig[n_run] = n_contig - 1;
            run_bin[n_run++] = bin;
            prev_bin = bin;
        }
        // positions only rise within a contig, so a window up to win_max has
        // its first record already: the one that reached win_max covers it
        const int64_t w_hi = (end - 1) >> 14;
        int64_t w = beg >> 14;
        if (w <= win_max) w = win_max + 1;
        if (w_hi - w >= win_cap - n_win) return -3;
        for (; w <= w_hi; ++w) {
            win_contig[n_win] = n_contig - 1;
            win[n_win] = w;
            win_start[n_win++] = start;
        }
        if (w_hi > win_max) win_max = w_hi;
    }
    if (off != body_len) return -1;
    counts[0] = n_contig;
    counts[1] = n_run;
    counts[2] = n_win;
    return 0;
}

}  // extern "C"
