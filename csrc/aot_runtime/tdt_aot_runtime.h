/* tdt_aot_runtime: Python-free execution of AOT-exported kernels on TPU.
 *
 * Reference analog: tools/runtime/triton_aot_runtime.cc — a dlopen-based
 * CUDA-driver stub layer + cubin loader so AOT-generated kernels run
 * without Python.  The TPU equivalent dlopens a PJRT plugin
 * (libtpu.so — `GetPjrtApi` is the stable C ABI the way
 * libcuda's driver API is), compiles the StableHLO bytecode that
 * triton_dist_tpu.tools.compile_aot exported, and executes it.
 *
 * Everything is plain C linkage so the library is usable from any host
 * language (and from ctypes, for tests).
 */
#ifndef TDT_AOT_RUNTIME_H_
#define TDT_AOT_RUNTIME_H_

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

typedef struct tdt_ctx tdt_ctx;

/* Element types, mirroring PJRT_Buffer_Type for the types our kernels use. */
typedef enum {
  TDT_INVALID = 0,
  TDT_PRED = 1,
  TDT_S8 = 2,
  TDT_S16 = 3,
  TDT_S32 = 4,
  TDT_S64 = 5,
  TDT_U8 = 6,
  TDT_U16 = 7,
  TDT_U32 = 8,
  TDT_U64 = 9,
  TDT_F16 = 10,
  TDT_F32 = 11,
  TDT_F64 = 12,
  TDT_BF16 = 13,
} tdt_dtype;

typedef struct {
  void* data;         /* host memory (caller-owned) */
  int64_t dims[8];
  int32_t ndims;
  tdt_dtype dtype;
  size_t nbytes;      /* size of `data` in bytes */
} tdt_buffer;

/* Client create option (PJRT_NamedValue).  `int_value` is used when
 * `is_int` is nonzero, else `str_value`. */
typedef struct {
  const char* name;
  const char* str_value;
  int64_t int_value;
  int32_t is_int;
} tdt_option;

/* dlopen `plugin_path`, resolve GetPjrtApi, initialize the plugin and
 * create a client.  `options` are plugin-specific client create options
 * (may be NULL).  Returns NULL on failure (see tdt_last_error()). */
tdt_ctx* tdt_init(const char* plugin_path);
tdt_ctx* tdt_init_with_options(const char* plugin_path,
                               const tdt_option* options, int n_options);

/* Load + compile a StableHLO module (`.mlir.bc` from compile_aot) with the
 * serialized CompileOptionsProto at `options_path`.  Returns an executable
 * handle >= 0, or -1 on failure. */
int tdt_load(tdt_ctx* ctx, const char* module_path, const char* options_path);

/* Number of outputs of a loaded executable, or -1. */
int tdt_num_outputs(tdt_ctx* ctx, int exec);

/* Execute: copies inputs host->device, runs, copies outputs device->host.
 * Caller allocates outputs[i].data with outputs[i].nbytes capacity.
 * Returns 0 on success. */
int tdt_execute(tdt_ctx* ctx, int exec, const tdt_buffer* inputs, int n_in,
                tdt_buffer* outputs, int n_out);

/* Human-readable platform string (e.g. "tpu"), valid until destroy. */
const char* tdt_platform(tdt_ctx* ctx);

const char* tdt_last_error(tdt_ctx* ctx);

void tdt_destroy(tdt_ctx* ctx);

/* dtype helpers */
size_t tdt_dtype_size(tdt_dtype t);
tdt_dtype tdt_dtype_from_name(const char* numpy_name); /* "float32" etc. */

#ifdef __cplusplus
}
#endif

#endif /* TDT_AOT_RUNTIME_H_ */
