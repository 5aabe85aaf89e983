// The wire fill — one pass from the native scan's arrays to the rows of a
// dispatch's staging buffer (variantcalling_tpu/wire.py has the layout and
// the numpy fill that writes the same bytes; tests/unit/test_wire.py holds
// the two equal).
//
// A row is `row_bytes` wide; `fields` lists (kind, byte offset, argument)
// per column of the layout. What each kind writes is what the Python
// featurization computes for that column, value for value:
//
// - POS: featurize.globalize_positions — the anchor's byte in the device
//   genome as one uint32; an unknown contig, a position under 1 or one at
//   least `radius` past its contig's end gets `pos_fill` (past the genome's
//   end: an all-N window).
// - QUAL, DP, SOR, GQ: the float64 column (GQ: the scan's float32, widened)
//   with NaN -> 0 unless `keep_nan`, cast to float32 (np.nan_to_num also
//   turns inf into DBL_MAX, which the float32 cast turns back into inf).
// - AF: featurize._compute_af in float32 — FORMAT AD alt/total where the
//   total is positive, else INFO AF — then NaN -> 0 and inf -> FLT_MAX
//   unless `keep_nan` (np.nan_to_num on a float32 array).
// - IS_HET: gt[0] != gt[1] and gt[1] >= 0. IS_SNP / IS_INDEL / IS_INS: bits
//   1 / 2 / 4 of the scan's allele class.
// - INDEL_LENGTH, N_ALTS: int32 as scanned. REF_CODE, ALT_CODE, INDEL_NUC:
//   base codes 0..4 in one byte.
// - EXTRA: float32 column `argument` of `extras`, made by Python (interval
//   membership, extra INFO keys), copied.
//
// With `keep_nan` and a `nan_cells` out-count, the float32 cells written as
// NaN (QUAL, DP, SOR, AF, GQ, EXTRA) are counted in the same pass: the
// missing values a default_left model routes. Without either nothing is
// counted.
//
// Single-threaded on purpose: the streaming executor's workers are the
// parallelism, and ctypes releases the interpreter for the whole call.

#include <cfloat>
#include <cmath>
#include <cstdint>
#include <cstring>

namespace {

enum Kind : int32_t {
    POS = 0, QUAL = 1, DP = 2, SOR = 3, AF = 4, GQ = 5, IS_HET = 6,
    IS_SNP = 7, IS_INDEL = 8, IS_INS = 9, INDEL_LENGTH = 10, REF_CODE = 11,
    ALT_CODE = 12, N_ALTS = 13, INDEL_NUC = 14, EXTRA = 15,
};

inline float from_f64(double v, bool keep_nan) {
    if (!keep_nan && std::isnan(v)) return 0.0f;
    return static_cast<float>(v);
}

template <typename T>
inline void put(uint8_t* row, T v) { std::memcpy(row, &v, sizeof(T)); }

// rows are written in blocks so a block's bytes stay in cache while every
// column passes over them
constexpr int64_t BLOCK = 2048;

}  // namespace

extern "C" {

// returns the number of rows written (hi - lo), <0 on bad arguments.
int64_t vctpu_wire_fill(
    uint8_t* dst,                // staging rows; row r begins at dst + r * row_bytes
    int64_t dst_row0,            // first row to write
    int64_t row_bytes,
    int64_t lo, int64_t hi,      // table rows [lo, hi)
    const int32_t* fields,       // (n_fields, 3): kind, offset, argument
    int64_t n_fields,
    const int64_t* pos,          // (n,) 1-based
    const int32_t* chrom_codes,  // (n,) index into contig_off / contig_len
    const int64_t* contig_off,   // (n_contigs,) first byte in the genome, <0 unknown
    const int64_t* contig_len,   // (n_contigs,)
    int64_t n_contigs, int64_t radius, uint32_t pos_fill,
    const double* qual,          // (n,)
    const int8_t* gt,            // (n, 2)
    const float* gq,             // (n,)
    const float* ad,             // (n, 3): ref, alt1, positive total
    const double* info_vals,     // (n, n_info)
    int64_t n_info, int32_t dp_col, int32_t sor_col, int32_t af_col,
    const uint8_t* aclass,       // (n,) bit 1 snp, 2 indel, 4 insertion
    const int32_t* indel_length, const int32_t* indel_nuc,
    const int32_t* ref_code, const int32_t* alt_code, const int32_t* n_alts,
    const float* const* extras,  // (n_extras) columns of (n,) float32
    int64_t n_extras, int32_t keep_nan,
    int64_t* nan_cells)          // out: NaN float cells written; null: not counted
{
    if (lo < 0 || hi < lo || row_bytes <= 0 || n_fields < 0) return -1;
    const bool keep = keep_nan != 0;
    const bool count = keep && nan_cells != nullptr;
    int64_t nans = 0;
    for (int64_t f = 0; f < n_fields; ++f) {
        const int32_t kind = fields[3 * f], off = fields[3 * f + 1], arg = fields[3 * f + 2];
        const int32_t size = (kind >= IS_HET && kind <= IS_INS) || kind == REF_CODE
            || kind == ALT_CODE || kind == INDEL_NUC ? 1 : 4;
        if (kind < POS || kind > EXTRA || off < 0 || off + size > row_bytes) return -2;
        if (kind == EXTRA && (arg < 0 || arg >= n_extras)) return -3;
        if ((kind == DP && (dp_col < 0 || dp_col >= n_info))
            || (kind == SOR && (sor_col < 0 || sor_col >= n_info))
            || (kind == AF && (af_col < 0 || af_col >= n_info))) return -4;
    }
    for (int64_t b_lo = lo; b_lo < hi; b_lo += BLOCK) {
        const int64_t b_hi = b_lo + BLOCK < hi ? b_lo + BLOCK : hi;
        uint8_t* base = dst + (dst_row0 + (b_lo - lo)) * row_bytes;
        for (int64_t f = 0; f < n_fields; ++f) {
            const int32_t kind = fields[3 * f], arg = fields[3 * f + 2];
            uint8_t* out = base + fields[3 * f + 1];
            switch (kind) {
            case POS:
                for (int64_t i = b_lo; i < b_hi; ++i, out += row_bytes) {
                    const int64_t pos0 = pos[i] - 1;
                    const int32_t c = chrom_codes[i];
                    const int64_t off = (c >= 0 && c < n_contigs) ? contig_off[c] : -1;
                    const bool bad = off < 0 || pos0 < 0 || pos0 >= contig_len[c] + radius;
                    put<uint32_t>(out, bad ? pos_fill : static_cast<uint32_t>(pos0 + off));
                }
                break;
            case QUAL:
                for (int64_t i = b_lo; i < b_hi; ++i, out += row_bytes) {
                    const float v = from_f64(qual[i], keep);
                    if (count) nans += std::isnan(v);
                    put<float>(out, v);
                }
                break;
            case DP: case SOR: {
                const int32_t col = kind == DP ? dp_col : sor_col;
                for (int64_t i = b_lo; i < b_hi; ++i, out += row_bytes) {
                    const float v = from_f64(info_vals[i * n_info + col], keep);
                    if (count) nans += std::isnan(v);
                    put<float>(out, v);
                }
                break;
            }
            case GQ:
                for (int64_t i = b_lo; i < b_hi; ++i, out += row_bytes) {
                    const float v = from_f64(static_cast<double>(gq[i]), keep);
                    if (count) nans += std::isnan(v);
                    put<float>(out, v);
                }
                break;
            case AF:
                for (int64_t i = b_lo; i < b_hi; ++i, out += row_bytes) {
                    const float ad1 = ad[3 * i + 1], ad2 = ad[3 * i + 2];
                    const float tot = std::isnan(ad2) ? 0.0f : ad2;
                    const float alt = (std::isnan(ad1) || ad1 < 0.0f) ? 0.0f : ad1;
                    float af = tot > 0.0f ? alt / std::fmax(tot, 1.0f) : NAN;
                    if (std::isnan(af)) af = static_cast<float>(info_vals[i * n_info + af_col]);
                    if (!keep) {
                        if (std::isnan(af)) af = 0.0f;
                        else if (std::isinf(af)) af = std::copysign(FLT_MAX, af);
                    }
                    if (count) nans += std::isnan(af);
                    put<float>(out, af);
                }
                break;
            case IS_HET:
                for (int64_t i = b_lo; i < b_hi; ++i, out += row_bytes)
                    *out = gt[2 * i] != gt[2 * i + 1] && gt[2 * i + 1] >= 0;
                break;
            case IS_SNP: case IS_INDEL: case IS_INS: {
                const uint8_t bit = kind == IS_SNP ? 1 : kind == IS_INDEL ? 2 : 4;
                for (int64_t i = b_lo; i < b_hi; ++i, out += row_bytes)
                    *out = (aclass[i] & bit) != 0;
                break;
            }
            case INDEL_LENGTH: case N_ALTS: {
                const int32_t* src = kind == INDEL_LENGTH ? indel_length : n_alts;
                for (int64_t i = b_lo; i < b_hi; ++i, out += row_bytes)
                    put<int32_t>(out, src[i]);
                break;
            }
            case REF_CODE: case ALT_CODE: case INDEL_NUC: {
                const int32_t* src = kind == REF_CODE ? ref_code
                    : kind == ALT_CODE ? alt_code : indel_nuc;
                for (int64_t i = b_lo; i < b_hi; ++i, out += row_bytes)
                    *out = static_cast<uint8_t>(src[i]);
                break;
            }
            case EXTRA: {
                const float* src = extras[arg];
                for (int64_t i = b_lo; i < b_hi; ++i, out += row_bytes) {
                    if (count) nans += std::isnan(src[i]);
                    put<float>(out, src[i]);
                }
                break;
            }
            }
        }
    }
    if (count) *nan_cells = nans;
    return hi - lo;
}

}  // extern "C"
