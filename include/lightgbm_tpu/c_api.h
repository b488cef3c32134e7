/*!
 * lightgbm_tpu native ABI — the subset of the fork's C/C++ API surface
 * that its cache-admission harness consumes
 * (reference: /root/reference/include/LightGBM/c_api.h:38,144-160,
 *  271,293-300,341-346,374,430,591-600,621-640,715-720 and the call
 *  sites in /root/reference/src/test.cpp:243-298).
 *
 * Signatures match the fork's header verbatim, including its
 * std::unordered_map<std::string, std::string> parameter passing (the
 * fork patched the upstream plain-C signatures to C++ maps), so
 * test.cpp-shaped code compiles against this header unchanged and links
 * against liblgbm_tpu.so, which embeds CPython and executes the
 * lightgbm_tpu runtime.
 */
#ifndef LIGHTGBM_TPU_C_API_H_
#define LIGHTGBM_TPU_C_API_H_

#include <cstdint>
#include <string>
#include <unordered_map>

#define LIGHTGBM_C_EXPORT extern "C" __attribute__((visibility("default")))
#define LIGHTGBM_CPP_EXPORT __attribute__((visibility("default")))

typedef void* DatasetHandle;
typedef void* BoosterHandle;

#define C_API_DTYPE_FLOAT32 (0)
#define C_API_DTYPE_FLOAT64 (1)
#define C_API_DTYPE_INT32   (2)
#define C_API_DTYPE_INT64   (3)

#define C_API_PREDICT_NORMAL     (0)
#define C_API_PREDICT_RAW_SCORE  (1)
#define C_API_PREDICT_LEAF_INDEX (2)
#define C_API_PREDICT_CONTRIB    (3)

LIGHTGBM_C_EXPORT const char* LGBM_GetLastError();

/* unordered_map parameters => C++ linkage, like the fork's header */
LIGHTGBM_CPP_EXPORT int LGBM_DatasetCreateFromCSR(
    const void* indptr, int indptr_type, const int32_t* indices,
    const void* data, int data_type, int64_t nindptr, int64_t nelem,
    int64_t num_col,
    std::unordered_map<std::string, std::string> parameters,
    const DatasetHandle reference, DatasetHandle* out);

LIGHTGBM_C_EXPORT int LGBM_DatasetSetField(DatasetHandle handle,
                                           const char* field_name,
                                           const void* field_data,
                                           int num_element, int type);

LIGHTGBM_C_EXPORT int LGBM_DatasetGetNumData(DatasetHandle handle,
                                             int64_t* out);

LIGHTGBM_C_EXPORT int LGBM_DatasetFree(DatasetHandle handle);

LIGHTGBM_CPP_EXPORT int LGBM_BoosterCreate(
    const DatasetHandle train_data,
    std::unordered_map<std::string, std::string> parameters,
    BoosterHandle* out);

LIGHTGBM_C_EXPORT int LGBM_BoosterFree(BoosterHandle handle);

LIGHTGBM_C_EXPORT int LGBM_BoosterUpdateOneIter(BoosterHandle handle,
                                                int* is_finished);

/* lightgbm_tpu extension (not in the fork's ABI): run num_iters
 * boosting iterations in fused device dispatches of up to `chunk`
 * whole iterations each.  Replaces an UpdateOneIter loop with one call
 * per retrain window so wall-clock tracks device throughput instead of
 * per-iteration host dispatch latency.  Sets *is_finished to 1 when
 * training stopped early (no more splittable leaves). */
LIGHTGBM_C_EXPORT int LGBM_BoosterUpdateChunked(BoosterHandle handle,
                                                int num_iters, int chunk,
                                                int* is_finished);

LIGHTGBM_C_EXPORT int LGBM_BoosterGetCurrentIteration(
    BoosterHandle handle, int64_t* out_iteration);

LIGHTGBM_C_EXPORT int LGBM_BoosterCalcNumPredict(BoosterHandle handle,
                                                 int num_row,
                                                 int predict_type,
                                                 int num_iteration,
                                                 int64_t* out_len);

LIGHTGBM_CPP_EXPORT int LGBM_BoosterPredictForCSR(
    BoosterHandle handle, const void* indptr, int indptr_type,
    const int32_t* indices, const void* data, int data_type,
    int64_t nindptr, int64_t nelem, int64_t num_col, int predict_type,
    int num_iteration,
    std::unordered_map<std::string, std::string> parameter,
    int64_t* out_len, double* out_result);

LIGHTGBM_C_EXPORT int LGBM_BoosterSaveModel(BoosterHandle handle,
                                            int start_iteration,
                                            int num_iteration,
                                            const char* filename);

/* ---------------------------------------------------------------------
 * Prediction server (lightgbm_tpu extension, not in the fork's ABI):
 * a hot-swap packed-ensemble predictor.  The windowed harness creates
 * ONE server, scores every request window against it, and swaps in
 * each freshly retrained booster — a swap whose padded model shape
 * matches the previous window re-dispatches into already-compiled
 * device programs (zero recompiles at steady state).  The server keeps
 * its own copy of the model, so the booster may be freed after a swap.
 * ------------------------------------------------------------------ */
typedef void* ServeHandle;

/* Recognized parameters: num_iteration_predict (served tree slice),
 * serve_max_batch / serve_max_wait_ms (micro-batch queue). */
LIGHTGBM_CPP_EXPORT int LGBM_ServeCreate(
    const BoosterHandle booster,
    std::unordered_map<std::string, std::string> parameters,
    ServeHandle* out);

LIGHTGBM_C_EXPORT int LGBM_ServeSwap(ServeHandle handle,
                                     const BoosterHandle booster);

LIGHTGBM_C_EXPORT int LGBM_ServeCalcNumPredict(ServeHandle handle,
                                               int num_row,
                                               int64_t* out_len);

/* predict_type: C_API_PREDICT_NORMAL or C_API_PREDICT_RAW_SCORE. */
LIGHTGBM_C_EXPORT int LGBM_ServePredictForCSR(
    ServeHandle handle, const void* indptr, int indptr_type,
    const int32_t* indices, const void* data, int data_type,
    int64_t nindptr, int64_t nelem, int64_t num_col, int predict_type,
    int64_t* out_len, double* out_result);

LIGHTGBM_C_EXPORT int LGBM_ServeFree(ServeHandle handle);

/* ---------------------------------------------------------------------
 * Model fleet (lightgbm_tpu extension, not in the fork's ABI): M
 * tenants stacked into ONE packed array family — a single jitted
 * program serves any (tenant_ids, rows) batch, and a per-tenant
 * retrain hands off via a zero-retrace device index write while the
 * other tenants keep answering (docs/Serving.md "Model fleets").
 * ------------------------------------------------------------------ */
typedef void* FleetHandle;

/* All num_tenants tenants start as copies of `booster`'s model;
 * specialize them with LGBM_FleetSwapTenant.  Recognized parameters:
 * num_iteration_predict, serve_replicas, fleet_value_dtype,
 * serve_max_batch / serve_max_wait_ms. */
LIGHTGBM_CPP_EXPORT int LGBM_FleetCreate(
    const BoosterHandle booster, int num_tenants,
    std::unordered_map<std::string, std::string> parameters,
    FleetHandle* out);

LIGHTGBM_C_EXPORT int LGBM_FleetSwapTenant(FleetHandle handle,
                                           int tenant_id,
                                           const BoosterHandle booster);

LIGHTGBM_C_EXPORT int LGBM_FleetCalcNumPredict(FleetHandle handle,
                                               int num_row,
                                               int64_t* out_len);

/* tenant_ids routes each CSR row to its tenant; num_tenant_ids == 1
 * broadcasts one tenant to the whole batch.  predict_type:
 * C_API_PREDICT_NORMAL or C_API_PREDICT_RAW_SCORE. */
LIGHTGBM_C_EXPORT int LGBM_FleetPredictForCSR(
    FleetHandle handle, const int32_t* tenant_ids,
    int64_t num_tenant_ids, const void* indptr, int indptr_type,
    const int32_t* indices, const void* data, int data_type,
    int64_t nindptr, int64_t nelem, int64_t num_col, int predict_type,
    int64_t* out_len, double* out_result);

LIGHTGBM_C_EXPORT int LGBM_FleetFree(FleetHandle handle);

/* ---------------------------------------------------------------------
 * AOT compile warmup (lightgbm_tpu extension, not in the fork's ABI):
 * precompile the declared (rows, features, parameters) training /
 * serving program families into the persistent XLA compile cache
 * (env JAX_COMPILATION_CACHE_DIR, else parameters key
 * compile_cache_dir, else <checkout>/.jax_cache),
 * so a deployment's FIRST real retrain window / first large predict
 * batch runs warm.  Call once at container start, before the request
 * loop; *out_num_compiled returns the number of fresh cache entries
 * written (0 = the cache was already warm for this declaration).
 * num_row <= 0 on WarmupServe warms the prediction server's default
 * row buckets.  See docs/ColdStart.md.
 * ------------------------------------------------------------------ */
LIGHTGBM_CPP_EXPORT int LGBM_WarmupTrain(
    std::unordered_map<std::string, std::string> parameters,
    int64_t num_row, int32_t num_feature, int* out_num_compiled);

LIGHTGBM_CPP_EXPORT int LGBM_WarmupServe(
    std::unordered_map<std::string, std::string> parameters,
    int64_t num_row, int32_t num_feature, int* out_num_compiled);

#endif  /* LIGHTGBM_TPU_C_API_H_ */
