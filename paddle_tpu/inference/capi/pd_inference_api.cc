// C inference API implementation — embeds CPython and drives
// paddle_tpu.inference (see pd_inference_api.h; reference:
// paddle/fluid/inference/capi_exp/pd_config.cc / pd_predictor.cc).
//
// Build (done by paddle_tpu.inference.capi.build_capi()):
//   g++ -O2 -fPIC -shared pd_inference_api.cc -o libpd_inference.so \
//       $(python3-config --includes) -lpython3.x

#include <Python.h>

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <string>

#include "pd_inference_api.h"

namespace {

std::string g_last_error;

void set_error_from_python() {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  if (value != nullptr) {
    PyObject* s = PyObject_Str(value);
    if (s != nullptr) {
      g_last_error = PyUnicode_AsUTF8(s);
      Py_DECREF(s);
    }
  } else {
    g_last_error = "unknown python error";
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
}

PyObject* g_inference_mod = nullptr;

PyObject* inference_module() {
  if (g_inference_mod == nullptr) {
    g_inference_mod = PyImport_ImportModule("paddle_tpu.inference");
    if (g_inference_mod == nullptr) set_error_from_python();
  }
  return g_inference_mod;
}

}  // namespace

struct PD_Config {
  PyObject* obj;  // paddle_tpu.inference.Config
};

struct PD_Predictor {
  PyObject* obj;  // paddle_tpu.inference.Predictor
};

extern "C" {

int PD_Init(void) {
  if (!Py_IsInitialized()) {
    Py_InitializeEx(0);
  }
  // Honor JAX_PLATFORMS even when the embedding host imported jax
  // before setting it (env alone is too late at that point — the
  // config route always works before first backend use).
  PyRun_SimpleString(
      "import os\n"
      "_p = os.environ.get('JAX_PLATFORMS')\n"
      "if _p:\n"
      "    import jax\n"
      "    jax.config.update('jax_platforms', _p.split(',')[0])\n");
  return inference_module() != nullptr ? 0 : 1;
}

void PD_Finalize(void) {
  g_inference_mod = nullptr;  // owned by the dying interpreter
  if (Py_IsInitialized()) Py_Finalize();
}

PD_Config* PD_ConfigCreate(void) {
  PyObject* mod = inference_module();
  if (mod == nullptr) return nullptr;
  PyObject* cfg = PyObject_CallMethod(mod, "Config", nullptr);
  if (cfg == nullptr) {
    set_error_from_python();
    return nullptr;
  }
  PD_Config* c = new PD_Config{cfg};
  return c;
}

void PD_ConfigSetModel(PD_Config* cfg, const char* prefix) {
  if (cfg == nullptr) return;
  PyObject* r =
      PyObject_CallMethod(cfg->obj, "set_prog_file", "s", prefix);
  if (r == nullptr)
    set_error_from_python();
  else
    Py_DECREF(r);
}

void PD_ConfigSetOptimCacheDir(PD_Config* cfg, const char* dir) {
  if (cfg == nullptr) return;
  PyObject* r =
      PyObject_CallMethod(cfg->obj, "set_optim_cache_dir", "s", dir);
  if (r == nullptr)
    set_error_from_python();
  else
    Py_DECREF(r);
}

void PD_ConfigDestroy(PD_Config* cfg) {
  if (cfg == nullptr) return;
  Py_XDECREF(cfg->obj);
  delete cfg;
}

PD_Predictor* PD_PredictorCreate(PD_Config* cfg) {
  PyObject* mod = inference_module();
  if (mod == nullptr || cfg == nullptr) return nullptr;
  PyObject* pred =
      PyObject_CallMethod(mod, "create_predictor", "O", cfg->obj);
  if (pred == nullptr) {
    set_error_from_python();
    return nullptr;
  }
  return new PD_Predictor{pred};
}

int PD_PredictorGetInputNum(PD_Predictor* pred) {
  if (pred == nullptr) return -1;
  PyObject* names = PyObject_CallMethod(pred->obj, "get_input_names",
                                        nullptr);
  if (names == nullptr) {
    set_error_from_python();
    return -1;
  }
  Py_ssize_t n = PyList_Size(names);
  Py_DECREF(names);
  return static_cast<int>(n);
}

void PD_PredictorDestroy(PD_Predictor* pred) {
  if (pred == nullptr) return;
  Py_XDECREF(pred->obj);
  delete pred;
}

int PD_PredictorRunFloat(PD_Predictor* pred, const float* const* in_data,
                         const int64_t* const* in_shapes,
                         const int* in_ndims, int n_inputs,
                         float** out_data, int64_t** out_shape,
                         int* out_ndim) {
  if (pred == nullptr) return 1;
  // marshal: numpy arrays via np.frombuffer(bytes).reshape(shape)
  PyObject* np = PyImport_ImportModule("numpy");
  if (np == nullptr) {
    set_error_from_python();
    return 1;
  }
  PyObject* inputs = PyList_New(n_inputs);
  for (int i = 0; i < n_inputs; ++i) {
    int64_t numel = 1;
    for (int d = 0; d < in_ndims[i]; ++d) numel *= in_shapes[i][d];
    PyObject* bytes = PyBytes_FromStringAndSize(
        reinterpret_cast<const char*>(in_data[i]),
        numel * sizeof(float));
    PyObject* flat = PyObject_CallMethod(np, "frombuffer", "Os", bytes,
                                         "float32");
    Py_DECREF(bytes);
    if (flat == nullptr) {
      set_error_from_python();
      Py_DECREF(inputs);
      Py_DECREF(np);
      return 1;
    }
    PyObject* shape = PyTuple_New(in_ndims[i]);
    for (int d = 0; d < in_ndims[i]; ++d)
      PyTuple_SET_ITEM(shape, d, PyLong_FromLongLong(in_shapes[i][d]));
    PyObject* arr =
        PyObject_CallMethod(flat, "reshape", "O", shape);
    Py_DECREF(flat);
    Py_DECREF(shape);
    if (arr == nullptr) {
      set_error_from_python();
      Py_DECREF(inputs);
      Py_DECREF(np);
      return 1;
    }
    PyList_SET_ITEM(inputs, i, arr);  // steals
  }
  PyObject* outs = PyObject_CallMethod(pred->obj, "run", "O", inputs);
  Py_DECREF(inputs);
  if (outs == nullptr) {
    set_error_from_python();
    Py_DECREF(np);
    return 1;
  }
  PyObject* first = PySequence_GetItem(outs, 0);
  Py_DECREF(outs);
  if (first == nullptr) {
    set_error_from_python();
    Py_DECREF(np);
    return 1;
  }
  // out = np.ascontiguousarray(first, 'float32'); bytes = out.tobytes()
  PyObject* arr = PyObject_CallMethod(np, "ascontiguousarray", "Os",
                                      first, "float32");
  Py_DECREF(first);
  Py_DECREF(np);
  if (arr == nullptr) {
    set_error_from_python();
    return 1;
  }
  PyObject* shape = PyObject_GetAttrString(arr, "shape");
  PyObject* bytes = PyObject_CallMethod(arr, "tobytes", nullptr);
  Py_DECREF(arr);
  if (shape == nullptr || bytes == nullptr) {
    set_error_from_python();
    Py_XDECREF(shape);
    Py_XDECREF(bytes);
    return 1;
  }
  int nd = static_cast<int>(PyTuple_Size(shape));
  *out_ndim = nd;
  *out_shape =
      static_cast<int64_t*>(malloc(sizeof(int64_t) * (nd > 0 ? nd : 1)));
  for (int d = 0; d < nd; ++d)
    (*out_shape)[d] = PyLong_AsLongLong(PyTuple_GET_ITEM(shape, d));
  Py_ssize_t blen = PyBytes_Size(bytes);
  *out_data = static_cast<float*>(malloc(blen > 0 ? blen : 1));
  std::memcpy(*out_data, PyBytes_AsString(bytes), blen);
  Py_DECREF(shape);
  Py_DECREF(bytes);
  return 0;
}

const char* PD_GetLastError(void) { return g_last_error.c_str(); }

void PD_Free(void* p) { free(p); }

}  // extern "C"
