// Shared wall-clock deadline for the C-ABI test binaries: a backend
// that never comes up degrades to a reported skip (exit 77, the
// automake convention) instead of hanging the build forever.
#pragma once

#include <signal.h>
#include <unistd.h>

#include <cstdlib>

namespace ptn_test {

inline const char*& deadline_name() {
  static const char* name = "test";
  return name;
}

// Async-signal-safe: write() + _exit() only.
inline void deadline_handler(int) {
  const char pre[] = "SKIP: ";
  const char post[] =
      " exceeded its wall-clock deadline (wedged backend?)\n";
  ssize_t ignored = write(2, pre, sizeof(pre) - 1);
  const char* n = deadline_name();
  size_t len = 0;
  while (n[len]) ++len;
  ignored = write(2, n, len);
  ignored = write(2, post, sizeof(post) - 1);
  (void)ignored;
  _exit(77);
}

// Default 540 s; override via PTN_TEST_DEADLINE_S. Non-numeric or
// non-positive values fall back to the default (alarm(0) would silently
// disable the guard).
inline void install_deadline(const char* test_name) {
  deadline_name() = test_name;
  signal(SIGALRM, deadline_handler);
  unsigned secs = 540;
  if (const char* env = std::getenv("PTN_TEST_DEADLINE_S")) {
    int v = std::atoi(env);
    if (v > 0) secs = (unsigned)v;
  }
  alarm(secs);
}

}  // namespace ptn_test
