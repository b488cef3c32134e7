/*!
 * test.cpp-shaped smoke harness: proves C++ code compiled against
 * include/lightgbm_tpu/c_api.h trains and predicts through the native
 * ABI the way the fork's cache-admission harness does
 * (/root/reference/src/test.cpp:243-298 trainModel / evaluateModel).
 *
 * Builds a synthetic windowed CSR matrix with the fork's feature layout
 * (HISTFEATURES gap features + size + cacheAvail + cost), trains a
 * binary booster per window (fresh booster for the second window, like
 * the fork's "train a new booster" branch), predicts the next window,
 * and checks the outputs are sane probabilities.  Exit 0 = pass.
 */
#include <chrono>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <random>
#include <string>
#include <unordered_map>
#include <vector>

#include "../../include/lightgbm_tpu/c_api.h"

#define HISTFEATURES 50

static std::unordered_map<std::string, std::string> trainParams = {
    {"boosting", "gbdt"},          {"objective", "binary"},
    {"max_bin", "255"},            {"num_iterations", "8"},
    {"learning_rate", "0.1"},      {"num_leaves", "31"},
    {"tree_learner", "serial"},    {"feature_fraction", "0.8"},
    {"bagging_freq", "5"},         {"bagging_fraction", "0.8"},
    {"min_data_in_leaf", "50"},    {"min_sum_hessian_in_leaf", "5.0"},
    {"verbosity", "-1"},
};

/* synthetic window: gap features correlated with the label, like
 * deriveFeatures' output shape (test.cpp:125-209) */
static void make_window(int rows, unsigned seed, std::vector<float>* labels,
                        std::vector<int32_t>* indptr,
                        std::vector<int32_t>* indices,
                        std::vector<double>* data) {
  std::mt19937 gen(seed);
  std::uniform_real_distribution<> uni(0.0, 1.0);
  std::uniform_int_distribution<> nhist(1, HISTFEATURES);
  indptr->push_back(0);
  for (int i = 0; i < rows; i++) {
    const bool hot = uni(gen) < 0.4;
    labels->push_back(hot ? 1.0f : 0.0f);
    const int k = nhist(gen);
    int32_t idx = 0;
    for (; idx < k; idx++) {
      const double base = hot ? 200.0 : 20000.0;
      indices->push_back(idx);
      data->push_back(base * (0.5 + uni(gen)));
    }
    indices->push_back(HISTFEATURES);
    data->push_back(std::round(100.0 * std::log2(64.0 + 4096.0 * uni(gen))));
    indices->push_back(HISTFEATURES + 1);
    data->push_back(std::round(100.0 * std::log2(1 << 30)));
    indices->push_back(HISTFEATURES + 2);
    data->push_back(1.0);
    indptr->push_back(indptr->back() + idx + 3);
  }
}

static int check(int rc, const char* what) {
  if (rc != 0) {
    std::fprintf(stderr, "FAIL %s: %s\n", what, LGBM_GetLastError());
    std::exit(1);
  }
  return rc;
}

int main() {
  const int rows = 4000;
  BoosterHandle booster = nullptr;
  ServeHandle server = nullptr;
  bool init = true;

  /* deployment-init AOT warmup (docs/ColdStart.md): precompile the
   * declared training + serving program families before the window
   * loop.  The persistent compile cache (JAX_COMPILATION_CACHE_DIR,
   * else <checkout>/.jax_cache) keeps the executables, so a RESTARTED
   * harness starts warm. */
  int warmed = -1;
  check(LGBM_WarmupTrain(trainParams, rows, HISTFEATURES + 3, &warmed),
        "WarmupTrain");
  std::printf("warmup: train programs compiled (%d fresh cache entries)\n",
              warmed);
  check(LGBM_WarmupServe(trainParams, 4096, HISTFEATURES + 3, &warmed),
        "WarmupServe");
  std::printf("warmup: serve programs compiled (%d fresh cache entries)\n",
              warmed);

  for (int window = 0; window < 2; window++) {
    std::vector<float> labels;
    std::vector<int32_t> indptr, indices;
    std::vector<double> data;
    make_window(rows, 7 + window, &labels, &indptr, &indices, &data);

    auto t0 = std::chrono::system_clock::now();
    DatasetHandle trainData;
    check(LGBM_DatasetCreateFromCSR(
              static_cast<void*>(indptr.data()), C_API_DTYPE_INT32,
              indices.data(), static_cast<void*>(data.data()),
              C_API_DTYPE_FLOAT64, indptr.size(), data.size(),
              HISTFEATURES + 3, trainParams, nullptr, &trainData),
          "DatasetCreateFromCSR");
    check(LGBM_DatasetSetField(trainData, "label",
                               static_cast<void*>(labels.data()),
                               labels.size(), C_API_DTYPE_FLOAT32),
          "DatasetSetField");
    int64_t ndata = 0;
    check(LGBM_DatasetGetNumData(trainData, &ndata), "GetNumData");
    if (ndata != rows) {
      std::fprintf(stderr, "FAIL num_data %lld != %d\n",
                   static_cast<long long>(ndata), rows);
      return 1;
    }

    /* fork pattern: first window trains `booster`; later windows train
     * a NEW booster and swap (test.cpp:256-293) */
    BoosterHandle target;
    check(LGBM_BoosterCreate(trainData, trainParams, &target),
          "BoosterCreate");
    /* fused driver: the whole window's iterations in chunked device
     * dispatches (falls back per-iteration when not eligible) */
    {
      int isFinished;
      check(LGBM_BoosterUpdateChunked(
                target, std::stoi(trainParams["num_iterations"]),
                /*chunk=*/10, &isFinished),
            "UpdateChunked");
    }
    /* serving hand-off: window 0 creates the prediction server, later
     * windows atomically swap in the freshly trained model (the server
     * keeps its own packed copy, so the old booster frees safely) */
    if (server == nullptr) {
      check(LGBM_ServeCreate(target, trainParams, &server), "ServeCreate");
    } else {
      check(LGBM_ServeSwap(server, target), "ServeSwap");
    }
    if (!init) {
      check(LGBM_BoosterFree(booster), "BoosterFree(old)");
    }
    booster = target;
    init = false;

    auto ms = std::chrono::duration_cast<std::chrono::milliseconds>(
                  std::chrono::system_clock::now() - t0)
                  .count();
    std::printf("window %d: trained %d rows in %lld ms\n", window, rows,
                static_cast<long long>(ms));

    /* evaluateModel pattern: predict the window through the booster */
    int64_t len = 0;
    check(LGBM_BoosterCalcNumPredict(booster, rows, C_API_PREDICT_NORMAL,
                                     0, &len),
          "CalcNumPredict");
    std::vector<double> result(len);
    check(LGBM_BoosterPredictForCSR(
              booster, static_cast<void*>(indptr.data()),
              C_API_DTYPE_INT32, indices.data(),
              static_cast<void*>(data.data()), C_API_DTYPE_FLOAT64,
              indptr.size(), data.size(), HISTFEATURES + 3,
              C_API_PREDICT_NORMAL, 0, trainParams, &len, result.data()),
          "PredictForCSR");
    if (len != rows) {
      std::fprintf(stderr, "FAIL predict len %lld != %d\n",
                   static_cast<long long>(len), rows);
      return 1;
    }
    int correct = 0;
    for (int i = 0; i < rows; i++) {
      if (result[i] < 0.0 || result[i] > 1.0 || result[i] != result[i]) {
        std::fprintf(stderr, "FAIL prob out of range: %f\n", result[i]);
        return 1;
      }
      if ((result[i] >= 0.5) == (labels[i] >= 0.5f)) correct++;
    }
    const double acc = static_cast<double>(correct) / rows;
    std::printf("window %d: train accuracy %.3f\n", window, acc);
    if (acc < 0.75) {
      std::fprintf(stderr, "FAIL accuracy %.3f < 0.75 — the planted "
                           "signal was not learned\n", acc);
      return 1;
    }

    /* the packed-ensemble server must agree with the booster walk
     * (float32 device accumulation => small value tolerance) */
    int64_t slen = 0;
    check(LGBM_ServeCalcNumPredict(server, rows, &slen),
          "ServeCalcNumPredict");
    std::vector<double> sresult(slen);
    check(LGBM_ServePredictForCSR(
              server, static_cast<void*>(indptr.data()),
              C_API_DTYPE_INT32, indices.data(),
              static_cast<void*>(data.data()), C_API_DTYPE_FLOAT64,
              indptr.size(), data.size(), HISTFEATURES + 3,
              C_API_PREDICT_NORMAL, &slen, sresult.data()),
          "ServePredictForCSR");
    if (slen != rows) {
      std::fprintf(stderr, "FAIL serve predict len %lld != %d\n",
                   static_cast<long long>(slen), rows);
      return 1;
    }
    for (int i = 0; i < rows; i++) {
      if (std::fabs(sresult[i] - result[i]) > 1e-4) {
        std::fprintf(stderr,
                     "FAIL serve/booster mismatch at %d: %f vs %f\n", i,
                     sresult[i], result[i]);
        return 1;
      }
    }
    std::printf("window %d: serve predict matches booster\n", window);

    /* model fleet: 2 tenants seeded/swapped from the same booster must
     * answer a mixed-tenant batch exactly like the solo server */
    if (window == 1) {
      FleetHandle fleet = nullptr;
      check(LGBM_FleetCreate(booster, 2, trainParams, &fleet),
            "FleetCreate");
      check(LGBM_FleetSwapTenant(fleet, 1, booster), "FleetSwapTenant");
      std::vector<int32_t> tenantIds(rows);
      for (int i = 0; i < rows; i++) tenantIds[i] = i % 2;
      int64_t flen = 0;
      check(LGBM_FleetCalcNumPredict(fleet, rows, &flen),
            "FleetCalcNumPredict");
      std::vector<double> fresult(flen);
      check(LGBM_FleetPredictForCSR(
                fleet, tenantIds.data(), rows,
                static_cast<void*>(indptr.data()), C_API_DTYPE_INT32,
                indices.data(), static_cast<void*>(data.data()),
                C_API_DTYPE_FLOAT64, indptr.size(), data.size(),
                HISTFEATURES + 3, C_API_PREDICT_NORMAL, &flen,
                fresult.data()),
            "FleetPredictForCSR");
      for (int i = 0; i < rows; i++) {
        if (std::fabs(fresult[i] - sresult[i]) > 1e-12) {
          std::fprintf(stderr,
                       "FAIL fleet/serve mismatch at %d: %f vs %f\n",
                       i, fresult[i], sresult[i]);
          return 1;
        }
      }
      check(LGBM_FleetFree(fleet), "FleetFree");
      std::printf("window %d: fleet predict matches serve\n", window);
    }
    check(LGBM_DatasetFree(trainData), "DatasetFree");
  }
  check(LGBM_BoosterSaveModel(booster, 0, -1, "/tmp/lgbm_capi_smoke.model"),
        "SaveModel");
  check(LGBM_ServeFree(server), "ServeFree");
  check(LGBM_BoosterFree(booster), "BoosterFree");
  std::printf("native ABI smoke: PASS\n");
  return 0;
}
