// C API implementation: embeds CPython and drives the inference
// Predictor (paddle_tpu.inference.create_predictor).
//
// Reference analog: paddle/fluid/inference/capi/pd_predictor.cc — there
// the C API wraps the C++ AnalysisPredictor directly; here the predictor
// is the XLA-compiled Python Predictor, so the shim owns an embedded
// interpreter (Py_Initialize once per process) and marshals tensors
// through numpy.  All entry points acquire the GIL — callable from any
// thread (cgo, pthreads).
//
// Build: make -C csrc libptpu_capi.so   (links libpython3.12)

#include "paddle_c_api.h"

#include <Python.h>

#include <cstdio>
#include <cstring>
#include <mutex>
#include <string>
#include <vector>

namespace {

thread_local std::string g_last_error;

void set_error(const std::string& msg) { g_last_error = msg; }

void set_py_error(const char* where) {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  std::string msg = std::string(where) + ": ";
  if (value) {
    PyObject* s = PyObject_Str(value);
    if (s) {
      msg += PyUnicode_AsUTF8(s);
      Py_DECREF(s);
    }
  } else {
    msg += "unknown python error";
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
  set_error(msg);
}

std::once_flag g_init_once;
bool g_init_ok = false;

struct OwnedTensor {
  std::string name;
  std::vector<int64_t> shape;
  std::vector<char> data;
  PD_DataType dtype;
};

const char* np_dtype_of(PD_DataType dt) {
  switch (dt) {
    case PD_FLOAT32: return "float32";
    case PD_INT32: return "int32";
    case PD_INT64: return "int64";
    case PD_UINT8: return "uint8";
  }
  return "float32";
}

size_t itemsize_of(PD_DataType dt) {
  switch (dt) {
    case PD_FLOAT32: case PD_INT32: return 4;
    case PD_INT64: return 8;
    case PD_UINT8: return 1;
  }
  return 4;
}

}  // namespace

struct PD_Predictor {
  PyObject* predictor = nullptr;          // paddle_tpu Predictor
  std::vector<std::string> input_names;
  std::vector<std::string> output_names;
  std::vector<OwnedTensor> outputs;       // last run's results
};

extern "C" {

int PD_Init(const char* platform) {
  std::call_once(g_init_once, [platform]() {
    if (!Py_IsInitialized()) {
      Py_InitializeEx(0);
    }
    PyGILState_STATE gil = PyGILState_Ensure();
    // force the XLA platform BEFORE jax initializes backends (serving
    // shims usually want cpu or an explicit chip)
    std::string code;
    const char* plat = platform;
    if (plat == nullptr) plat = std::getenv("PD_CAPI_PLATFORM");
    if (plat != nullptr && plat[0] != '\0') {
      code = std::string(
                 "import os\nos.environ['JAX_PLATFORMS'] = '") + plat +
             "'\nimport jax\njax.config.update('jax_platforms', '" + plat +
             "')\n";
    }
    code += "import numpy\nimport paddle_tpu.inference\n";
    if (PyRun_SimpleString(code.c_str()) != 0) {
      set_error("PD_Init: failed to import paddle_tpu.inference "
                "(set PYTHONPATH to the framework root)");
      g_init_ok = false;
    } else {
      g_init_ok = true;
    }
    // hand the GIL to the "main" thread state so other threads can take it
    PyGILState_Release(gil);
    if (g_init_ok) {
      (void)PyEval_SaveThread();
    }
  });
  return g_init_ok ? 0 : -1;
}

PD_Predictor* PD_NewPredictor(const char* model_prefix) {
  if (PD_Init(nullptr) != 0) return nullptr;
  PyGILState_STATE gil = PyGILState_Ensure();
  PD_Predictor* out = nullptr;
  PyObject *mod = nullptr, *cfg = nullptr, *pred = nullptr, *names = nullptr;
  do {
    mod = PyImport_ImportModule("paddle_tpu.inference");
    if (!mod) { set_py_error("import paddle_tpu.inference"); break; }
    cfg = PyObject_CallMethod(mod, "Config", "s", model_prefix);
    if (!cfg) { set_py_error("Config"); break; }
    pred = PyObject_CallMethod(mod, "create_predictor", "O", cfg);
    if (!pred) { set_py_error("create_predictor"); break; }
    out = new PD_Predictor();
    out->predictor = pred;
    pred = nullptr;
    for (int which = 0; which < 2; ++which) {
      names = PyObject_CallMethod(
          out->predictor,
          which == 0 ? "get_input_names" : "get_output_names", nullptr);
      if (!names) { set_py_error("get names"); break; }
      Py_ssize_t n = PySequence_Size(names);
      for (Py_ssize_t i = 0; i < n; ++i) {
        PyObject* item = PySequence_GetItem(names, i);
        const char* s = PyUnicode_AsUTF8(item);
        (which == 0 ? out->input_names : out->output_names)
            .push_back(s ? s : "");
        Py_XDECREF(item);
      }
      Py_CLEAR(names);
    }
  } while (false);
  Py_XDECREF(names);
  Py_XDECREF(pred);
  Py_XDECREF(cfg);
  Py_XDECREF(mod);
  if (out && !out->predictor) { delete out; out = nullptr; }
  PyGILState_Release(gil);
  return out;
}

void PD_DeletePredictor(PD_Predictor* pred) {
  if (!pred) return;
  PyGILState_STATE gil = PyGILState_Ensure();
  Py_XDECREF(pred->predictor);
  PyGILState_Release(gil);
  delete pred;
}

int PD_GetInputNum(PD_Predictor* pred) {
  return pred ? static_cast<int>(pred->input_names.size()) : -1;
}

int PD_GetOutputNum(PD_Predictor* pred) {
  return pred ? static_cast<int>(pred->output_names.size()) : -1;
}

const char* PD_GetInputName(PD_Predictor* pred, int index) {
  if (!pred || index < 0 ||
      index >= static_cast<int>(pred->input_names.size()))
    return nullptr;
  return pred->input_names[index].c_str();
}

const char* PD_GetOutputName(PD_Predictor* pred, int index) {
  if (!pred || index < 0 ||
      index >= static_cast<int>(pred->output_names.size()))
    return nullptr;
  return pred->output_names[index].c_str();
}

int PD_PredictorRun(PD_Predictor* pred, const PD_Tensor* inputs,
                    int n_inputs) {
  if (!pred || !pred->predictor) {
    set_error("PD_PredictorRun: null predictor");
    return -1;
  }
  PyGILState_STATE gil = PyGILState_Ensure();
  int rc = -1;
  PyObject *np = nullptr, *arg_list = nullptr, *result = nullptr;
  // Any failure below must not leave a previous run's tensors served by
  // PD_GetOutputTensor as if they were this run's.
  pred->outputs.clear();
  do {
    np = PyImport_ImportModule("numpy");
    if (!np) { set_py_error("import numpy"); break; }
    arg_list = PyList_New(n_inputs);
    bool ok = true;
    for (int i = 0; i < n_inputs; ++i) {
      const PD_Tensor& t = inputs[i];
      size_t count = 1;
      PyObject* shape = PyTuple_New(t.ndim);
      for (int d = 0; d < t.ndim; ++d) {
        count *= static_cast<size_t>(t.shape[d]);
        PyTuple_SetItem(shape, d, PyLong_FromLongLong(t.shape[d]));
      }
      PyObject* bytes = PyBytes_FromStringAndSize(
          static_cast<const char*>(t.data), count * itemsize_of(t.dtype));
      // numpy.frombuffer(bytes, dtype).reshape(shape).copy()
      PyObject* flat = PyObject_CallMethod(np, "frombuffer", "Os", bytes,
                                           np_dtype_of(t.dtype));
      Py_DECREF(bytes);
      if (!flat) { set_py_error("frombuffer"); Py_DECREF(shape);
                   ok = false; break; }
      PyObject* arr = PyObject_CallMethod(flat, "reshape", "O", shape);
      Py_DECREF(flat);
      Py_DECREF(shape);
      if (!arr) { set_py_error("reshape"); ok = false; break; }
      PyList_SetItem(arg_list, i, arr);  // steals
    }
    if (!ok) break;
    result = PyObject_CallMethod(pred->predictor, "run", "O", arg_list);
    if (!result) { set_py_error("Predictor.run"); break; }
    Py_ssize_t n_out = PySequence_Size(result);
    if (n_out < 0) {  // non-sequence: report, don't throw across the C ABI
      set_py_error("Predictor.run returned a non-sequence");
      break;
    }
    // Convert into a local vector and swap in only on full success:
    // a mid-loop failure must not leave PD_GetOutputTensor serving
    // partially-built (empty-shape / garbage-dtype) tensors with rc 0.
    std::vector<OwnedTensor> converted(n_out);
    for (Py_ssize_t i = 0; i < n_out; ++i) {
      PyObject* o = PySequence_GetItem(result, i);
      PyObject* arr = PyObject_CallMethod(
          np, "ascontiguousarray", "O", o);
      Py_XDECREF(o);
      if (!arr) { set_py_error("ascontiguousarray"); ok = false; break; }
      OwnedTensor& ot = converted[i];
      PyObject* dt = PyObject_GetAttrString(arr, "dtype");
      PyObject* dts = PyObject_Str(dt);
      std::string dtype_s = PyUnicode_AsUTF8(dts);
      Py_XDECREF(dts);
      Py_XDECREF(dt);
      if (dtype_s == "float32") ot.dtype = PD_FLOAT32;
      else if (dtype_s == "int32") ot.dtype = PD_INT32;
      else if (dtype_s == "int64") ot.dtype = PD_INT64;
      else if (dtype_s == "uint8") ot.dtype = PD_UINT8;
      else {
        // re-cast anything else (e.g. bfloat16 outputs) to float32
        PyObject* cast = PyObject_CallMethod(arr, "astype", "s",
                                             "float32");
        Py_DECREF(arr);
        if (!cast) { set_py_error("astype"); ok = false; break; }
        arr = cast;
        ot.dtype = PD_FLOAT32;
      }
      PyObject* shp = PyObject_GetAttrString(arr, "shape");
      Py_ssize_t nd = PyTuple_Size(shp);
      size_t count = 1;
      for (Py_ssize_t d = 0; d < nd; ++d) {
        int64_t dim = PyLong_AsLongLong(PyTuple_GetItem(shp, d));
        ot.shape.push_back(dim);
        count *= static_cast<size_t>(dim);
      }
      Py_XDECREF(shp);
      PyObject* buf = PyObject_CallMethod(arr, "tobytes", nullptr);
      Py_DECREF(arr);
      if (!buf) { set_py_error("tobytes"); ok = false; break; }
      char* raw = nullptr;
      Py_ssize_t len = 0;
      PyBytes_AsStringAndSize(buf, &raw, &len);
      ot.data.assign(raw, raw + len);
      Py_DECREF(buf);
      if (i < static_cast<Py_ssize_t>(pred->output_names.size()))
        ot.name = pred->output_names[i];
    }
    if (!ok) break;  // outputs already cleared above
    pred->outputs.swap(converted);
    rc = 0;
  } while (false);
  Py_XDECREF(result);
  Py_XDECREF(arg_list);
  Py_XDECREF(np);
  PyGILState_Release(gil);
  return rc;
}

int PD_GetOutputTensor(PD_Predictor* pred, int index, PD_Tensor* out) {
  if (!pred || !out || index < 0 ||
      index >= static_cast<int>(pred->outputs.size())) {
    set_error("PD_GetOutputTensor: bad index (run the predictor first)");
    return -1;
  }
  const OwnedTensor& ot = pred->outputs[index];
  out->dtype = ot.dtype;
  out->ndim = static_cast<int>(ot.shape.size());
  out->shape = ot.shape.data();
  out->data = ot.data.data();
  return 0;
}

const char* PD_GetLastError(void) { return g_last_error.c_str(); }

}  // extern "C"
