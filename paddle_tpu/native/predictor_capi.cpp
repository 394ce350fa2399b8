// C-callable serving API over the AOT StableHLO artifact.
//
// ≙ the reference's C/C++ inference surface: PaddlePredictor::Run
// (paddle/contrib/inference/paddle_inference_api.h:46) and the capi
// shims (paddle/capi/). The TPU-native artifact is a jax.export
// StableHLO program (io.py export_serving_model); this library embeds
// CPython to deserialize and invoke it, marshalling only flat buffers
// across the C boundary — the compute itself is the compiled XLA
// program, the interpreter only shuttles bytes.
//
// Threading: single-threaded by design (one embedded interpreter, GIL
// held by the caller's thread). Outputs are owned by the predictor and
// valid until the next pt_predictor_run / pt_predictor_destroy.
//
// Build: paddle_tpu.native.load_library("predictor_capi", python_flags)
// or any `g++ -shared -fPIC $(python3-config --includes --embed --ldflags)`.

#include <Python.h>

#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

namespace {

std::string g_error;

void set_error_from_python() {
  PyObject *type = nullptr, *value = nullptr, *trace = nullptr;
  PyErr_Fetch(&type, &value, &trace);
  PyErr_NormalizeException(&type, &value, &trace);
  g_error = "python error";
  if (value != nullptr) {
    PyObject* s = PyObject_Str(value);
    if (s != nullptr) {
      const char* c = PyUnicode_AsUTF8(s);
      if (c != nullptr) g_error = c;
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(trace);
}

struct Output {
  std::vector<char> raw;       // fetch bytes in the fetch's OWN dtype
  std::string dtype;           // numpy dtype name ("float32", "int32", ...)
  std::vector<int64_t> shape;
  std::vector<float> fcache;   // lazy float32 view for the legacy accessor
};

struct Predictor {
  long handle = 0;
  PyObject* module = nullptr;  // borrowed ref to paddle_tpu.serving_embed
  std::vector<Output> outputs;
};

template <typename T>
void widen_to_float(const char* raw, size_t n, std::vector<float>* dst) {
  const T* src = reinterpret_cast<const T*>(raw);
  dst->resize(n);
  for (size_t k = 0; k < n; ++k) (*dst)[k] = static_cast<float>(src[k]);
}

void write_shape(const Output& out, int64_t* shape_out, int* ndim) {
  *ndim = static_cast<int>(out.shape.size());
  for (size_t d = 0; d < out.shape.size() && d < 8; ++d) {
    shape_out[d] = out.shape[d];
  }
}

PyObject* serving_module() {
  if (!Py_IsInitialized()) {
    // Py_Initialize honors PYTHONPATH, which must make paddle_tpu
    // importable
    Py_InitializeEx(0);
  }
  PyObject* mod = PyImport_ImportModule("paddle_tpu.serving_embed");
  if (mod == nullptr) set_error_from_python();
  return mod;
}

}  // namespace

extern "C" {

const char* pt_last_error() { return g_error.c_str(); }

void* pt_predictor_create(const char* model_dir) {
  g_error.clear();
  PyObject* mod = serving_module();
  if (mod == nullptr) return nullptr;
  PyObject* h = PyObject_CallMethod(mod, "create", "s", model_dir);
  if (h == nullptr) {
    set_error_from_python();
    Py_DECREF(mod);
    return nullptr;
  }
  Predictor* p = new Predictor();
  p->handle = PyLong_AsLong(h);
  p->module = mod;
  Py_DECREF(h);
  return p;
}

// feeds: n_feeds flat buffers; dtype 0 = float32, 1 = int64.
// Returns 0 on success; pt_last_error() explains failures.
int pt_predictor_run(void* pred, const void* const* feed_data,
                     const int64_t* const* feed_shapes, const int* feed_ndims,
                     const int* feed_dtypes, int n_feeds) {
  g_error.clear();
  Predictor* p = static_cast<Predictor*>(pred);
  PyObject* feeds = PyList_New(n_feeds);
  for (int i = 0; i < n_feeds; ++i) {
    int64_t elems = 1;
    PyObject* shape = PyTuple_New(feed_ndims[i]);
    for (int d = 0; d < feed_ndims[i]; ++d) {
      elems *= feed_shapes[i][d];
      PyTuple_SET_ITEM(shape, d, PyLong_FromLongLong(feed_shapes[i][d]));
    }
    const int64_t nbytes = elems * (feed_dtypes[i] == 0 ? 4 : 8);
    PyObject* raw = PyBytes_FromStringAndSize(
        static_cast<const char*>(feed_data[i]), nbytes);
    PyObject* dtype =
        PyUnicode_FromString(feed_dtypes[i] == 0 ? "float32" : "int64");
    PyObject* entry = PyTuple_Pack(3, raw, shape, dtype);
    Py_DECREF(raw);
    Py_DECREF(shape);
    Py_DECREF(dtype);
    PyList_SET_ITEM(feeds, i, entry);  // steals entry
  }
  PyObject* result =
      PyObject_CallMethod(p->module, "run", "lO", p->handle, feeds);
  Py_DECREF(feeds);
  if (result == nullptr) {
    set_error_from_python();
    return 1;
  }
  p->outputs.clear();
  const Py_ssize_t n_out = PyList_Size(result);
  for (Py_ssize_t i = 0; i < n_out; ++i) {
    // (bytes, shape, dtype_name); pre-dtype-protocol builds sent 2-tuples
    // of float32 bytes — tolerate both
    PyObject* entry = PyList_GetItem(result, i);
    PyObject* raw = PyTuple_GetItem(entry, 0);
    PyObject* shape = PyTuple_GetItem(entry, 1);
    Output out;
    out.dtype = "float32";
    if (PyTuple_Size(entry) >= 3) {
      const char* dt = PyUnicode_AsUTF8(PyTuple_GetItem(entry, 2));
      if (dt != nullptr) {
        out.dtype = dt;
      } else {
        PyErr_Clear();  // non-str dtype slot: keep the float32 fallback
      }
    }
    const Py_ssize_t ndim = PyTuple_Size(shape);
    for (Py_ssize_t d = 0; d < ndim; ++d) {
      out.shape.push_back(PyLong_AsLongLong(PyTuple_GetItem(shape, d)));
    }
    const char* buf = PyBytes_AsString(raw);
    const Py_ssize_t nbytes = PyBytes_Size(raw);
    out.raw.resize(nbytes);
    std::memcpy(out.raw.data(), buf, nbytes);
    p->outputs.push_back(std::move(out));
  }
  Py_DECREF(result);
  return 0;
}

int pt_predictor_num_outputs(void* pred) {
  return static_cast<int>(static_cast<Predictor*>(pred)->outputs.size());
}

// Dtype-preserving accessor: the i-th output's RAW bytes in its own
// dtype; writes rank to *ndim, up to 8 dims to shape_out, and the numpy
// dtype name to *dtype_out (owned by the predictor). Valid until the
// next run/destroy.
const void* pt_predictor_output_ex(void* pred, int i, int64_t* shape_out,
                                   int* ndim, const char** dtype_out) {
  Predictor* p = static_cast<Predictor*>(pred);
  if (i < 0 || i >= static_cast<int>(p->outputs.size())) return nullptr;
  const Output& out = p->outputs[i];
  write_shape(out, shape_out, ndim);
  if (dtype_out != nullptr) *dtype_out = out.dtype.c_str();
  return out.raw.data();
}

// Legacy float32 accessor: returns the i-th output as float32, converting
// integer/double fetches on demand (pre-dtype-protocol clients assumed
// float everywhere — keep them working). Unconvertible dtypes return
// nullptr; use pt_predictor_output_ex for the raw bytes. Valid until the
// next run/destroy.
const float* pt_predictor_output(void* pred, int i, int64_t* shape_out,
                                 int* ndim) {
  Predictor* p = static_cast<Predictor*>(pred);
  if (i < 0 || i >= static_cast<int>(p->outputs.size())) return nullptr;
  Output& out = p->outputs[i];
  write_shape(out, shape_out, ndim);
  if (out.dtype == "float32") {
    return reinterpret_cast<const float*>(out.raw.data());
  }
  if (out.fcache.empty()) {
    if (out.dtype == "int32") {
      widen_to_float<int32_t>(out.raw.data(), out.raw.size() / 4,
                              &out.fcache);
    } else if (out.dtype == "int64") {
      widen_to_float<int64_t>(out.raw.data(), out.raw.size() / 8,
                              &out.fcache);
    } else if (out.dtype == "float64") {
      widen_to_float<double>(out.raw.data(), out.raw.size() / 8,
                             &out.fcache);
    } else if (out.dtype == "uint8") {
      widen_to_float<uint8_t>(out.raw.data(), out.raw.size(), &out.fcache);
    } else if (out.dtype == "bool") {
      widen_to_float<int8_t>(out.raw.data(), out.raw.size(), &out.fcache);
    } else {
      g_error = "pt_predictor_output: cannot widen dtype '" + out.dtype +
                "' to float32; use pt_predictor_output_ex";
      return nullptr;
    }
  }
  return out.fcache.data();
}

void pt_predictor_destroy(void* pred) {
  Predictor* p = static_cast<Predictor*>(pred);
  if (p == nullptr) return;
  if (p->module != nullptr) {
    PyObject* r =
        PyObject_CallMethod(p->module, "destroy", "l", p->handle);
    Py_XDECREF(r);
    Py_DECREF(p->module);
  }
  delete p;
}

}  // extern "C"
