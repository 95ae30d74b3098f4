// Shared CPython-embedding plumbing for the C-ABI entry points
// (trainer.cc, predictor.cc): GIL RAII, python-error capture, and the
// interpreter bootstrap. The embedding direction mirrors the
// reference's train/demo + inference/capi split over one runtime.
#pragma once

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cstdlib>
#include <string>

namespace ptn_embed {

// GIL helper working both embedded (we own the interpreter) and hosted
// (the .so was ctypes-loaded inside a running Python).
class Gil {
 public:
  Gil() : state_(PyGILState_Ensure()) {}
  ~Gil() { PyGILState_Release(state_); }

 private:
  PyGILState_STATE state_;
};

// Per-thread last-error string; each entry-point family exposes its own
// *_last_error() that reads this.
inline std::string& last_error() {
  thread_local std::string err;
  return err;
}

inline void capture_py_error(const char* where) {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  PyObject* s = value ? PyObject_Str(value) : nullptr;
  const char* msg = s ? PyUnicode_AsUTF8(s) : nullptr;
  if (!msg) {
    // PyUnicode_AsUTF8 can itself fail (non-UTF-8 surrogates); never
    // concatenate NULL into std::string
    PyErr_Clear();
    msg = "unknown python error";
  }
  last_error() = std::string(where) + ": " + msg;
  Py_XDECREF(s);
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
}

// Interpreter bootstrap: no-op when hosted inside a running Python;
// when embedding, pins JAX to the CPU backend unless
// PTN_TRAINER_KEEP_PLATFORM is set (an embedded trainer is a side
// process, and a chip belongs to one process at a time). Prepends
// repo_root to sys.path and imports `module` as a smoke check.
// Returns 0 / -1.
inline int bootstrap(const char* repo_root, const char* module) {
  bool embedded = false;
  if (!Py_IsInitialized()) {
    if (!std::getenv("PTN_TRAINER_KEEP_PLATFORM"))
      setenv("JAX_PLATFORMS", "cpu", 1);
    Py_InitializeEx(0);
    embedded = true;
  }
  int rc = 0;
  {
    Gil gil;
    if (embedded && !std::getenv("PTN_TRAINER_KEEP_PLATFORM")) {
      // Belt to the env var's braces: jax.config.update wins over
      // anything that set the platform during interpreter start,
      // because no XLA client exists yet (tests/conftest.py does the
      // same).
      if (PyRun_SimpleString(
              "import jax\n"
              "jax.config.update('jax_platforms', 'cpu')\n") != 0) {
        last_error() = "bootstrap: failed to pin jax to the cpu backend";
        rc = -1;
      }
    }
    PyObject* sys_path = PySys_GetObject("path");  // borrowed
    if (repo_root && *repo_root) {
      PyObject* p = PyUnicode_FromString(repo_root);
      PyList_Insert(sys_path, 0, p);
      Py_DECREF(p);
    }
    if (rc == 0) {
      PyObject* mod = PyImport_ImportModule(module);
      if (!mod) {
        capture_py_error(module);
        rc = -1;
      } else {
        Py_DECREF(mod);
      }
    }
  }
  if (embedded) {
    // Release the GIL the init thread acquired with Py_InitializeEx so
    // other C threads can enter via PyGILState_Ensure.
    PyEval_SaveThread();
  }
  return rc;
}

}  // namespace ptn_embed
