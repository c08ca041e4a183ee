// dl4j-tpu native runtime: C++ wrappers over the PJRT C API.
//
// Reference parity: libnd4j's NativeOps/LaunchContext layer — the C++
// runtime under the JVM that owns device handles, buffers, executables
// and a compile cache (SURVEY.md §2.1 "L0 native math core", §7 item 1:
// "the only mandatory C++ component").
//
// TPU-native shape: where libnd4j implements kernels, HERE the compiler
// (XLA, behind the PJRT plugin .so) owns the kernels; the native layer's
// job is the RUNTIME — plugin loading, client/device lifetime, host<->
// device transfers, StableHLO compilation with an in-memory executable
// cache, and synchronous execution. Exposed as a flat C ABI consumed by
// ctypes (no pybind11 in this image).
//
// Build: `make` in deeplearning4j_tpu/native (g++ -shared -fPIC); the only
// compile-time dependency is the PJRT C API header; the plugin (any PJRT
// C-API plugin, named by the caller) is dlopen'd at runtime.

#include <dlfcn.h>
#include <stdint.h>
#include <string.h>

#include <cstdio>
#include <map>
#include <mutex>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

// ----------------------------------------------------------------- helpers

void set_err(char* err, size_t errlen, const std::string& msg) {
  if (err && errlen > 0) {
    snprintf(err, errlen, "%s", msg.c_str());
  }
}

// Take ownership of a PJRT_Error, extract its message, destroy it.
std::string consume_error(const PJRT_Api* api, PJRT_Error* e) {
  if (!e) return "";
  PJRT_Error_Message_Args margs;
  memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = e;
  api->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = e;
  api->PJRT_Error_Destroy(&dargs);
  return msg;
}

// Block on an event, consume it, return error message ("" = ok).
std::string await_event(const PJRT_Api* api, PJRT_Event* event) {
  if (!event) return "";
  PJRT_Event_Await_Args aargs;
  memset(&aargs, 0, sizeof(aargs));
  aargs.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aargs.event = event;
  std::string msg = consume_error(api, api->PJRT_Event_Await(&aargs));
  PJRT_Event_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = event;
  api->PJRT_Event_Destroy(&dargs);
  return msg;
}

uint64_t fnv1a(const char* data, size_t n, uint64_t seed = 1469598103934665603ull) {
  uint64_t h = seed;
  for (size_t i = 0; i < n; ++i) {
    h ^= static_cast<unsigned char>(data[i]);
    h *= 1099511628211ull;
  }
  return h;
}

// ------------------------------------------------------------------ client

struct Dl4jClient {
  void* dl_handle = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  std::vector<PJRT_Device*> devices;   // addressable
  // compile cache: hash(program bytes, options bytes) -> loaded executable
  std::map<uint64_t, PJRT_LoadedExecutable*> cache;
  std::mutex mu;
  size_t cache_hits = 0;
  size_t cache_misses = 0;
};

struct Dl4jExecutable {
  Dl4jClient* owner = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  size_t num_outputs = 0;
};

}  // namespace

extern "C" {

void dl4j_client_destroy(void* vc);  // forward

// Output buffer descriptor handed back to Python (dense, major-to-minor).
typedef struct {
  void* data;        // malloc'd; free via dl4j_free_outputs
  int32_t dtype;     // PJRT_Buffer_Type
  int32_t ndim;
  int64_t dims[16];
  int64_t nbytes;
} Dl4jHostBuffer;

// ---- client lifecycle ----------------------------------------------------

// Create options: n_opts parallel arrays. types[i]: 0 = string, 1 = int64.
// (Some PJRT plugins require NamedValue create options, mirroring what
// jax's plugin registration passes them.)
void* dl4j_client_create(const char* plugin_path, int n_opts,
                         const char* const* opt_keys,
                         const int32_t* opt_types,
                         const char* const* opt_strs,
                         const int64_t* opt_ints, char* err, size_t errlen) {
  void* h = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!h) {
    set_err(err, errlen, std::string("dlopen failed: ") + dlerror());
    return nullptr;
  }
  typedef const PJRT_Api* (*GetPjrtApiFn)();
  GetPjrtApiFn get_api =
      reinterpret_cast<GetPjrtApiFn>(dlsym(h, "GetPjrtApi"));
  if (!get_api) {
    set_err(err, errlen, "plugin exports no GetPjrtApi symbol");
    dlclose(h);
    return nullptr;
  }
  const PJRT_Api* api = get_api();
  if (!api) {
    set_err(err, errlen, "GetPjrtApi returned null");
    dlclose(h);
    return nullptr;
  }

  if (api->PJRT_Plugin_Initialize) {
    PJRT_Plugin_Initialize_Args iargs;
    memset(&iargs, 0, sizeof(iargs));
    iargs.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    std::string msg = consume_error(api, api->PJRT_Plugin_Initialize(&iargs));
    if (!msg.empty()) {
      set_err(err, errlen, "PJRT_Plugin_Initialize: " + msg);
      dlclose(h);
      return nullptr;
    }
  }

  std::vector<PJRT_NamedValue> named(n_opts);
  for (int i = 0; i < n_opts; ++i) {
    PJRT_NamedValue& nv = named[i];
    memset(&nv, 0, sizeof(nv));
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.name = opt_keys[i];
    nv.name_size = strlen(opt_keys[i]);
    if (opt_types[i] == 0) {
      nv.type = PJRT_NamedValue_kString;
      nv.string_value = opt_strs[i];
      nv.value_size = strlen(opt_strs[i]);
    } else {
      nv.type = PJRT_NamedValue_kInt64;
      nv.int64_value = opt_ints[i];
      nv.value_size = 1;
    }
  }

  PJRT_Client_Create_Args cargs;
  memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = named.data();
  cargs.num_options = n_opts;
  std::string msg = consume_error(api, api->PJRT_Client_Create(&cargs));
  if (!msg.empty()) {
    set_err(err, errlen, "PJRT_Client_Create: " + msg);
    dlclose(h);
    return nullptr;
  }

  Dl4jClient* c = new Dl4jClient();
  c->dl_handle = h;
  c->api = api;
  c->client = cargs.client;

  PJRT_Client_AddressableDevices_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.client = c->client;
  msg = consume_error(api, api->PJRT_Client_AddressableDevices(&dargs));
  if (!msg.empty()) {
    set_err(err, errlen, "AddressableDevices: " + msg);
    dl4j_client_destroy(c);
    return nullptr;
  }
  c->devices.assign(dargs.addressable_devices,
                    dargs.addressable_devices + dargs.num_addressable_devices);
  if (c->devices.empty()) {
    set_err(err, errlen, "client has no addressable devices");
    dl4j_client_destroy(c);
    return nullptr;
  }
  return c;
}

void dl4j_client_destroy(void* vc) {
  Dl4jClient* c = static_cast<Dl4jClient*>(vc);
  if (!c) return;
  for (auto& kv : c->cache) {
    PJRT_LoadedExecutable_Destroy_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
    args.executable = kv.second;
    consume_error(c->api, c->api->PJRT_LoadedExecutable_Destroy(&args));
  }
  if (c->client) {
    PJRT_Client_Destroy_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    args.client = c->client;
    consume_error(c->api, c->api->PJRT_Client_Destroy(&args));
  }
  // NOTE: the plugin .so stays loaded for process lifetime (unloading XLA
  // runtimes mid-process is unsafe); we intentionally skip dlclose.
  delete c;
}

int dl4j_client_device_count(void* vc) {
  Dl4jClient* c = static_cast<Dl4jClient*>(vc);
  return c ? static_cast<int>(c->devices.size()) : 0;
}

int dl4j_client_platform_name(void* vc, char* out, size_t outlen) {
  Dl4jClient* c = static_cast<Dl4jClient*>(vc);
  if (!c) return -1;
  PJRT_Client_PlatformName_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  args.client = c->client;
  std::string msg = consume_error(c->api, c->api->PJRT_Client_PlatformName(&args));
  if (!msg.empty()) return -1;
  size_t n = args.platform_name_size < outlen - 1 ? args.platform_name_size
                                                  : outlen - 1;
  memcpy(out, args.platform_name, n);
  out[n] = '\0';
  return static_cast<int>(n);
}

int dl4j_client_api_version(void* vc, int* major, int* minor) {
  Dl4jClient* c = static_cast<Dl4jClient*>(vc);
  if (!c) return -1;
  *major = c->api->pjrt_api_version.major_version;
  *minor = c->api->pjrt_api_version.minor_version;
  return 0;
}

// ---- compile (with in-memory executable cache) ---------------------------

void* dl4j_compile(void* vc, const char* code, int64_t code_size,
                   const char* format,          // "mlir" | "hlo"
                   const char* options, int64_t options_size,
                   int* cache_hit, char* err, size_t errlen) {
  Dl4jClient* c = static_cast<Dl4jClient*>(vc);
  if (!c) {
    set_err(err, errlen, "null client");
    return nullptr;
  }
  uint64_t key = fnv1a(code, code_size);
  key = fnv1a(options ? options : "", options_size, key);
  key = fnv1a(format, strlen(format), key);

  {
    std::lock_guard<std::mutex> lock(c->mu);
    auto it = c->cache.find(key);
    if (it != c->cache.end()) {
      c->cache_hits++;
      if (cache_hit) *cache_hit = 1;
      Dl4jExecutable* e = new Dl4jExecutable();
      e->owner = c;
      e->exec = it->second;
      PJRT_LoadedExecutable_GetExecutable_Args ga;
      memset(&ga, 0, sizeof(ga));
      ga.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
      ga.loaded_executable = e->exec;
      consume_error(c->api, c->api->PJRT_LoadedExecutable_GetExecutable(&ga));
      PJRT_Executable_NumOutputs_Args na;
      memset(&na, 0, sizeof(na));
      na.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
      na.executable = ga.executable;
      consume_error(c->api, c->api->PJRT_Executable_NumOutputs(&na));
      e->num_outputs = na.num_outputs;
      return e;
    }
  }
  if (cache_hit) *cache_hit = 0;

  PJRT_Program program;
  memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = const_cast<char*>(code);
  program.code_size = code_size;
  program.format = format;
  program.format_size = strlen(format);

  PJRT_Client_Compile_Args args;
  memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  args.client = c->client;
  args.program = &program;
  args.compile_options = options;
  args.compile_options_size = options_size;
  std::string msg = consume_error(c->api, c->api->PJRT_Client_Compile(&args));
  if (!msg.empty()) {
    set_err(err, errlen, "compile failed: " + msg);
    return nullptr;
  }

  Dl4jExecutable* e = new Dl4jExecutable();
  e->owner = c;
  e->exec = args.executable;

  PJRT_LoadedExecutable_GetExecutable_Args ga;
  memset(&ga, 0, sizeof(ga));
  ga.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  ga.loaded_executable = e->exec;
  msg = consume_error(c->api, c->api->PJRT_LoadedExecutable_GetExecutable(&ga));
  if (msg.empty()) {
    PJRT_Executable_NumOutputs_Args na;
    memset(&na, 0, sizeof(na));
    na.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
    na.executable = ga.executable;
    msg = consume_error(c->api, c->api->PJRT_Executable_NumOutputs(&na));
    if (msg.empty()) e->num_outputs = na.num_outputs;
  }

  {
    std::lock_guard<std::mutex> lock(c->mu);
    c->cache_misses++;
    c->cache[key] = e->exec;
  }
  return e;
}

void dl4j_executable_release(void* ve) {
  // The LoadedExecutable itself is owned by the client cache; this only
  // frees the handle wrapper.
  delete static_cast<Dl4jExecutable*>(ve);
}

int64_t dl4j_executable_num_outputs(void* ve) {
  Dl4jExecutable* e = static_cast<Dl4jExecutable*>(ve);
  return e ? static_cast<int64_t>(e->num_outputs) : -1;
}

int64_t dl4j_client_cache_stats(void* vc, int64_t* hits, int64_t* misses) {
  Dl4jClient* c = static_cast<Dl4jClient*>(vc);
  if (!c) return -1;
  std::lock_guard<std::mutex> lock(c->mu);
  if (hits) *hits = c->cache_hits;
  if (misses) *misses = c->cache_misses;
  return static_cast<int64_t>(c->cache.size());
}

// ---- execute -------------------------------------------------------------

static size_t dtype_nbytes(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_PRED:
    case PJRT_Buffer_Type_S8:
    case PJRT_Buffer_Type_U8:
      return 1;
    case PJRT_Buffer_Type_S16:
    case PJRT_Buffer_Type_U16:
    case PJRT_Buffer_Type_F16:
    case PJRT_Buffer_Type_BF16:
      return 2;
    case PJRT_Buffer_Type_S32:
    case PJRT_Buffer_Type_U32:
    case PJRT_Buffer_Type_F32:
      return 4;
    case PJRT_Buffer_Type_S64:
    case PJRT_Buffer_Type_U64:
    case PJRT_Buffer_Type_F64:
    case PJRT_Buffer_Type_C64:
      return 8;
    case PJRT_Buffer_Type_C128:
      return 16;
    default:
      return 0;
  }
}

// Synchronous single-device execute: host inputs in, host outputs out.
// inputs: n_in descriptors {data, dtype, ndim, dims}.
int dl4j_execute(void* ve, int n_in, void** in_data, const int32_t* in_dtypes,
                 const int32_t* in_ndims, const int64_t* in_dims_flat,
                 int device_ordinal, Dl4jHostBuffer* outs, int max_outs,
                 char* err, size_t errlen) {
  Dl4jExecutable* e = static_cast<Dl4jExecutable*>(ve);
  if (!e) {
    set_err(err, errlen, "null executable");
    return -1;
  }
  Dl4jClient* c = e->owner;
  const PJRT_Api* api = c->api;
  if (device_ordinal < 0 ||
      device_ordinal >= static_cast<int>(c->devices.size())) {
    set_err(err, errlen, "device ordinal out of range");
    return -1;
  }
  PJRT_Device* device = c->devices[device_ordinal];

  // 1) host -> device transfers
  std::vector<PJRT_Buffer*> arg_bufs;
  arg_bufs.reserve(n_in);
  const int64_t* dims_cursor = in_dims_flat;
  std::string msg;
  for (int i = 0; i < n_in; ++i) {
    PJRT_Client_BufferFromHostBuffer_Args h2d;
    memset(&h2d, 0, sizeof(h2d));
    h2d.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    h2d.client = c->client;
    h2d.data = in_data[i];
    h2d.type = static_cast<PJRT_Buffer_Type>(in_dtypes[i]);
    h2d.dims = dims_cursor;
    h2d.num_dims = in_ndims[i];
    h2d.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    h2d.device = device;
    dims_cursor += in_ndims[i];
    msg = consume_error(api, api->PJRT_Client_BufferFromHostBuffer(&h2d));
    if (!msg.empty()) {
      set_err(err, errlen, "BufferFromHostBuffer: " + msg);
      goto fail_inputs;
    }
    // wait until the runtime is done with the host memory
    msg = await_event(api, h2d.done_with_host_buffer);
    if (!msg.empty()) {
      set_err(err, errlen, "h2d transfer: " + msg);
      goto fail_inputs;
    }
    arg_bufs.push_back(h2d.buffer);
  }

  {
    // 2) execute
    size_t n_out = e->num_outputs;
    if (static_cast<int>(n_out) > max_outs) {
      set_err(err, errlen, "output count exceeds caller capacity");
      goto fail_inputs;
    }
    std::vector<PJRT_Buffer*> out_bufs(n_out, nullptr);
    PJRT_Buffer** out_list = out_bufs.data();
    PJRT_Buffer* const* arg_list = arg_bufs.data();
    PJRT_Event* device_complete = nullptr;

    PJRT_ExecuteOptions opts;
    memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

    PJRT_LoadedExecutable_Execute_Args ex;
    memset(&ex, 0, sizeof(ex));
    ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    ex.executable = e->exec;
    ex.options = &opts;
    ex.argument_lists = &arg_list;
    ex.num_devices = 1;
    ex.num_args = n_in;
    ex.output_lists = &out_list;
    ex.device_complete_events = &device_complete;
    ex.execute_device = device;
    msg = consume_error(api, api->PJRT_LoadedExecutable_Execute(&ex));
    if (!msg.empty()) {
      set_err(err, errlen, "Execute: " + msg);
      goto fail_inputs;
    }
    msg = await_event(api, device_complete);
    if (!msg.empty()) {
      set_err(err, errlen, "execution: " + msg);
      for (auto* b : out_bufs)
        if (b) {
          PJRT_Buffer_Destroy_Args da;
          memset(&da, 0, sizeof(da));
          da.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
          da.buffer = b;
          consume_error(api, api->PJRT_Buffer_Destroy(&da));
        }
      goto fail_inputs;
    }

    // 3) device -> host for each output
    for (size_t o = 0; o < n_out; ++o) {
      PJRT_Buffer* buf = out_bufs[o];
      Dl4jHostBuffer* hb = &outs[o];
      memset(hb, 0, sizeof(*hb));

      PJRT_Buffer_ElementType_Args ta;
      memset(&ta, 0, sizeof(ta));
      ta.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
      ta.buffer = buf;
      consume_error(api, api->PJRT_Buffer_ElementType(&ta));
      hb->dtype = ta.type;

      PJRT_Buffer_Dimensions_Args dda;
      memset(&dda, 0, sizeof(dda));
      dda.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
      dda.buffer = buf;
      consume_error(api, api->PJRT_Buffer_Dimensions(&dda));
      hb->ndim = static_cast<int32_t>(dda.num_dims);
      int64_t numel = 1;
      for (size_t d = 0; d < dda.num_dims && d < 16; ++d) {
        hb->dims[d] = dda.dims[d];
        numel *= dda.dims[d];
      }

      PJRT_Buffer_ToHostBuffer_Args d2h;
      memset(&d2h, 0, sizeof(d2h));
      d2h.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
      d2h.src = buf;
      d2h.dst = nullptr;  // query size
      msg = consume_error(api, api->PJRT_Buffer_ToHostBuffer(&d2h));
      size_t need = d2h.dst_size;
      if (!msg.empty() || need == 0) {
        // fall back to dense size from dtype * numel
        need = dtype_nbytes(static_cast<PJRT_Buffer_Type>(hb->dtype)) * numel;
      }
      hb->data = malloc(need);
      hb->nbytes = need;
      memset(&d2h, 0, sizeof(d2h));
      d2h.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
      d2h.src = buf;
      d2h.dst = hb->data;
      d2h.dst_size = need;
      msg = consume_error(api, api->PJRT_Buffer_ToHostBuffer(&d2h));
      if (msg.empty()) msg = await_event(api, d2h.event);

      PJRT_Buffer_Destroy_Args da;
      memset(&da, 0, sizeof(da));
      da.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      da.buffer = buf;
      consume_error(api, api->PJRT_Buffer_Destroy(&da));

      if (!msg.empty()) {
        set_err(err, errlen, "d2h transfer: " + msg);
        for (size_t k = 0; k <= o; ++k)
          if (outs[k].data) {
            free(outs[k].data);
            outs[k].data = nullptr;
          }
        for (size_t k = o + 1; k < n_out; ++k) {
          PJRT_Buffer_Destroy_Args da2;
          memset(&da2, 0, sizeof(da2));
          da2.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
          da2.buffer = out_bufs[k];
          consume_error(api, api->PJRT_Buffer_Destroy(&da2));
        }
        goto fail_inputs;
      }
    }

    // success: free input device buffers
    for (auto* b : arg_bufs) {
      PJRT_Buffer_Destroy_Args da;
      memset(&da, 0, sizeof(da));
      da.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      da.buffer = b;
      consume_error(api, api->PJRT_Buffer_Destroy(&da));
    }
    return static_cast<int>(n_out);
  }

fail_inputs:
  for (auto* b : arg_bufs) {
    PJRT_Buffer_Destroy_Args da;
    memset(&da, 0, sizeof(da));
    da.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    da.buffer = b;
    consume_error(api, api->PJRT_Buffer_Destroy(&da));
  }
  return -1;
}

void dl4j_free_outputs(Dl4jHostBuffer* outs, int n) {
  for (int i = 0; i < n; ++i) {
    if (outs[i].data) {
      free(outs[i].data);
      outs[i].data = nullptr;
    }
  }
}

}  // extern "C"
