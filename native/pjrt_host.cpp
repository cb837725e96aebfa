// Native PJRT-C-API serving host: dlopen a PJRT plugin, create a client,
// compile a StableHLO module, execute, read results back — no Python
// interpreter anywhere on the serving path.
//
// This is the native-host half of the export contract (models/export.py
// emits the StableHLO program + serialized CompileOptionsProto bundle;
// SURVEY §2 "Native components"; the plugin this image ships is the
// libtpu wheel's libtpu.so). The reference's serving host is native
// too (Rust control plane + libtorch C++, services.rs:513-524); this is
// the TPU-shaped equivalent: the PJRT C API is the stable ABI every XLA
// plugin exports.
//
// Usage:
//   pjrt_host probe <plugin.so>
//       dlopen + GetPjrtApi + version + attributes + client-create attempt;
//       prints one JSON object. Never crashes on an un-creatable client —
//       the report IS the product (the committed deferral evidence).
//   pjrt_host run <plugin.so> <bundle_dir> [--options client_options.txt]
//       bundle_dir holds program.mlir, compile_options.pb, and an args.txt
//       manifest ("dtype:d0,d1,...[=raw_file]" per executable input, so
//       weights ship as raw files SEPARATE from the program, exactly like
//       the SDFS deployment). create client -> compile -> stage args ->
//       one execution -> print output shapes and leading values as JSON.
//   pjrt_host serve <plugin.so> <bundle_dir> [--dir d] [--repeat N] ...
//       the RESIDENT serving loop (reference: the native member loads its
//       models once at boot and answers predict forever,
//       services.rs:475-497,513-524): boot + compile + stage weights ONCE,
//       then decode JPEGs with the in-process native decoder
//       (image_pipeline.cpp, linked into this binary), stage u8 batches,
//       execute, and emit top-1/prob — first over --dir if given, then
//       request-per-line on stdin until EOF. --repeat N measures the
//       sustained JPEG->top-1 rate with decode pipelined against device
//       execution (same depth idea as run's --iters mode).
//   pjrt_host stage <bundle_dir> --dir d --out staged.raw
//       hermetic half of serve (no plugin, no TPU): decode --dir into the
//       manifest's image-arg layout (pad by repetition like the exporter)
//       and write the exact bytes serve would hand BufferFromHostBuffer —
//       the decode->staging contract a CPU-only test can pin.
//
// Build: make pjrt_host (needs the PJRT C API header shipped inside the
// tensorflow wheel; see Makefile's include-path discovery).

#include <dlfcn.h>
#include <dirent.h>
#include <unistd.h>
#include <ctime>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <cstdint>
#include <algorithm>
#include <array>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

// Native JPEG decode + resize (image_pipeline.cpp, linked into this
// binary) — the same code path the Python ctypes binding serves from.
extern "C" int dmlc_decode_resize_batch(const char** paths, int n, int size,
                                        uint8_t* out, int* status,
                                        int n_threads);

namespace {

const PJRT_Api* g_api = nullptr;

std::string ErrMessage(PJRT_Error* err) {
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  g_api->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  g_api->PJRT_Error_Destroy(&dargs);
  return msg;
}

// JSON string escaping for error messages we embed in the report.
std::string JsonEscape(const std::string& s) {
  std::string out;
  for (char c : s) {
    if (c == '"' || c == '\\') { out += '\\'; out += c; }
    else if (c == '\n') out += "\\n";
    else if (static_cast<unsigned char>(c) < 0x20) out += ' ';
    else out += c;
  }
  return out;
}

#define CHECK_PJRT(expr)                                            \
  do {                                                              \
    PJRT_Error* _err = (expr);                                      \
    if (_err != nullptr) {                                          \
      std::fprintf(stderr, "pjrt_host: %s failed: %s\n", #expr,     \
                   ErrMessage(_err).c_str());                       \
      return 1;                                                     \
    }                                                               \
  } while (0)

std::vector<char> ReadFile(const char* path) {
  std::vector<char> out;
  FILE* f = std::fopen(path, "rb");
  if (!f) { std::fprintf(stderr, "pjrt_host: cannot open %s\n", path); std::exit(1); }
  std::fseek(f, 0, SEEK_END);
  long n = std::ftell(f);
  std::fseek(f, 0, SEEK_SET);
  out.resize(n);
  if (n && std::fread(out.data(), 1, n, f) != static_cast<size_t>(n)) {
    std::fprintf(stderr, "pjrt_host: short read on %s\n", path);
    std::exit(1);
  }
  std::fclose(f);
  return out;
}

const PJRT_Api* LoadApi(const char* so_path, std::string* error) {
  void* handle = dlopen(so_path, RTLD_NOW | RTLD_LOCAL);
  if (!handle) { *error = dlerror(); return nullptr; }
  using GetPjrtApiFn = const PJRT_Api* (*)();
  auto get = reinterpret_cast<GetPjrtApiFn>(dlsym(handle, "GetPjrtApi"));
  if (!get) { *error = "no GetPjrtApi symbol"; return nullptr; }
  const PJRT_Api* api = get();
  if (!api) { *error = "GetPjrtApi returned null"; return nullptr; }
  return api;
}

struct DtypeSpec {
  PJRT_Buffer_Type type;
  size_t bytes;
  const char* name;
};

bool ParseDtype(const std::string& s, DtypeSpec* out) {
  if (s == "u8") { *out = {PJRT_Buffer_Type_U8, 1, "u8"}; return true; }
  if (s == "f32") { *out = {PJRT_Buffer_Type_F32, 4, "f32"}; return true; }
  if (s == "i32") { *out = {PJRT_Buffer_Type_S32, 4, "i32"}; return true; }
  if (s == "bf16") { *out = {PJRT_Buffer_Type_BF16, 2, "bf16"}; return true; }
  return false;
}

// Client-create options file: one `name=i:<int>` or `name=s:<string>` per
// line. Plugin-specific and optional: the operator supplies it with
// --options or drops client_options.txt into the bundle.
struct Options {
  std::vector<PJRT_NamedValue> values;
  std::vector<std::string> storage;  // stable backing for names/strings
  std::vector<int64_t> ints;
};

bool LoadOptions(const char* path, Options* out) {
  FILE* f = std::fopen(path, "rb");
  if (!f) return false;
  char line[1024];
  // Two passes' worth of stable storage: reserve so pointers survive.
  std::vector<std::array<std::string, 2>> raw;
  while (std::fgets(line, sizeof(line), f)) {
    std::string s(line);
    while (!s.empty() && (s.back() == '\n' || s.back() == '\r')) s.pop_back();
    if (s.empty() || s[0] == '#') continue;
    auto eq = s.find('=');
    if (eq == std::string::npos || eq + 2 >= s.size() || s[eq + 2] != ':') {
      std::fprintf(stderr, "pjrt_host: bad options line: %s\n", s.c_str());
      std::fclose(f);
      return false;
    }
    raw.push_back({s.substr(0, eq), s.substr(eq + 1)});
  }
  std::fclose(f);
  out->storage.reserve(raw.size() * 2);
  out->ints.reserve(raw.size());
  for (auto& kv : raw) {
    out->storage.push_back(kv[0]);
    const std::string& name = out->storage.back();
    PJRT_NamedValue nv;
    std::memset(&nv, 0, sizeof(nv));
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.name = name.c_str();
    nv.name_size = name.size();
    char kind = kv[1][0];
    std::string val = kv[1].substr(2);
    if (kind == 'i') {
      out->ints.push_back(std::atoll(val.c_str()));
      nv.type = PJRT_NamedValue_kInt64;
      nv.int64_value = out->ints.back();
      nv.value_size = 1;
    } else if (kind == 's') {
      // Pool sessions must be fresh PER INVOCATION, not per export: a
      // bundle is run many times (weights republish without re-export),
      // and reusing a baked session id would collide in the pool
      // allocator. The exporter writes a base id; we uniquify it here.
      if (name == "session_id")
        val += "-" + std::to_string(getpid()) + "-" + std::to_string(time(nullptr));
      out->storage.push_back(val);
      nv.type = PJRT_NamedValue_kString;
      nv.string_value = out->storage.back().c_str();
      nv.value_size = out->storage.back().size();
    } else {
      std::fprintf(stderr, "pjrt_host: bad option kind %c\n", kind);
      return false;
    }
    out->values.push_back(nv);
  }
  return true;
}

int AwaitEvent(PJRT_Event* event) {
  PJRT_Event_Await_Args args;
  std::memset(&args, 0, sizeof(args));
  args.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  args.event = event;
  PJRT_Error* err = g_api->PJRT_Event_Await(&args);
  PJRT_Event_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = event;
  g_api->PJRT_Event_Destroy(&dargs);
  if (err) {
    std::fprintf(stderr, "pjrt_host: event failed: %s\n", ErrMessage(err).c_str());
    return 1;
  }
  return 0;
}

int Probe(const char* so_path, const char* options_path) {
  Options opts;
  if (options_path && !LoadOptions(options_path, &opts)) return 1;
  std::printf("{\"plugin\": \"%s\"", JsonEscape(so_path).c_str());
  std::string error;
  g_api = LoadApi(so_path, &error);
  if (!g_api) {
    std::printf(", \"loaded\": false, \"error\": \"%s\"}\n", JsonEscape(error).c_str());
    return 0;
  }
  std::printf(", \"loaded\": true, \"api_version\": \"%d.%d\"",
              g_api->pjrt_api_version.major_version,
              g_api->pjrt_api_version.minor_version);

  {
    PJRT_Plugin_Initialize_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    PJRT_Error* err = g_api->PJRT_Plugin_Initialize(&args);
    std::printf(", \"plugin_initialize\": \"%s\"",
                err ? JsonEscape(ErrMessage(err)).c_str() : "ok");
  }
  {
    PJRT_Plugin_Attributes_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Plugin_Attributes_Args_STRUCT_SIZE;
    PJRT_Error* err = g_api->PJRT_Plugin_Attributes(&args);
    if (!err) {
      std::printf(", \"attributes\": {");
      for (size_t i = 0; i < args.num_attributes; ++i) {
        const PJRT_NamedValue& nv = args.attributes[i];
        std::printf("%s\"%s\": ", i ? ", " : "",
                    JsonEscape(std::string(nv.name, nv.name_size)).c_str());
        if (nv.type == PJRT_NamedValue_kString)
          std::printf("\"%s\"",
                      JsonEscape(std::string(nv.string_value, nv.value_size)).c_str());
        else if (nv.type == PJRT_NamedValue_kInt64)
          std::printf("%lld", static_cast<long long>(nv.int64_value));
        else if (nv.type == PJRT_NamedValue_kInt64List) {
          std::printf("[");
          for (size_t j = 0; j < nv.value_size; ++j)
            std::printf("%s%lld", j ? ", " : "", static_cast<long long>(nv.int64_array_value[j]));
          std::printf("]");
        } else
          std::printf("null");
      }
      std::printf("}");
    } else {
      std::printf(", \"attributes_error\": \"%s\"", JsonEscape(ErrMessage(err)).c_str());
    }
  }

  PJRT_Client_Create_Args cargs;
  std::memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = opts.values.data();
  cargs.num_options = opts.values.size();
  PJRT_Error* err = g_api->PJRT_Client_Create(&cargs);
  if (err) {
    std::printf(", \"client_create\": \"%s\"}\n", JsonEscape(ErrMessage(err)).c_str());
    return 0;
  }
  PJRT_Client* client = cargs.client;

  PJRT_Client_PlatformName_Args pargs;
  std::memset(&pargs, 0, sizeof(pargs));
  pargs.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  pargs.client = client;
  if (PJRT_Error* e = g_api->PJRT_Client_PlatformName(&pargs))
    ErrMessage(e);  // destroys; probe continues
  else
    std::printf(", \"platform\": \"%.*s\"", static_cast<int>(pargs.platform_name_size),
                pargs.platform_name);

  PJRT_Client_Devices_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_Devices_Args_STRUCT_SIZE;
  dargs.client = client;
  if (PJRT_Error* e = g_api->PJRT_Client_Devices(&dargs))
    ErrMessage(e);
  else
    std::printf(", \"num_devices\": %zu", dargs.num_devices);

  PJRT_Client_Destroy_Args xargs;
  std::memset(&xargs, 0, sizeof(xargs));
  xargs.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
  xargs.client = client;
  g_api->PJRT_Client_Destroy(&xargs);
  std::printf(", \"client_create\": \"ok\"}\n");
  return 0;
}

// One execution dispatch: fresh output buffers + completion event.
PJRT_Error* DispatchExec(PJRT_LoadedExecutable* exec, PJRT_ExecuteOptions* eopts,
                         PJRT_Buffer* const* const* arg_lists, size_t num_args,
                         std::vector<PJRT_Buffer*>* outs, PJRT_Event** ev) {
  PJRT_Buffer** out_lists[1] = {outs->data()};
  PJRT_Event* evs[1] = {nullptr};
  PJRT_LoadedExecutable_Execute_Args ea;
  std::memset(&ea, 0, sizeof(ea));
  ea.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ea.executable = exec;
  ea.options = eopts;
  ea.argument_lists = arg_lists;
  ea.num_devices = 1;
  ea.num_args = num_args;
  ea.output_lists = out_lists;
  ea.device_complete_events = evs;
  PJRT_Error* err = g_api->PJRT_LoadedExecutable_Execute(&ea);
  *ev = evs[0];
  return err;
}

void DestroyBuffer(PJRT_Buffer* b) {
  if (!b) return;  // error paths destroy output vectors that never filled in
  PJRT_Buffer_Destroy_Args bd;
  std::memset(&bd, 0, sizeof(bd));
  bd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  bd.buffer = b;
  g_api->PJRT_Buffer_Destroy(&bd);
}

void DestroyBuffers(const std::vector<PJRT_Buffer*>& bufs) {
  for (PJRT_Buffer* b : bufs) DestroyBuffer(b);
}

// Copy one buffer to host (a true end-of-work barrier even on a plugin
// whose completion events resolve at dispatch-ack). Returns nonzero on
// failure; on success `host` holds the bytes.
int ReadbackBuffer(PJRT_Buffer* buf, std::vector<char>* host) {
  PJRT_Buffer_ToHostBuffer_Args th;
  std::memset(&th, 0, sizeof(th));
  th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  th.src = buf;
  PJRT_Error* err = g_api->PJRT_Buffer_ToHostBuffer(&th);  // size query
  if (err) { std::fprintf(stderr, "pjrt_host: size query failed: %s\n", ErrMessage(err).c_str()); return 1; }
  host->resize(th.dst_size);
  th.dst = host->data();
  err = g_api->PJRT_Buffer_ToHostBuffer(&th);
  if (err) { std::fprintf(stderr, "pjrt_host: readback failed: %s\n", ErrMessage(err).c_str()); return 1; }
  return AwaitEvent(th.event);
}

// One executable argument, parsed from the bundle's args.txt manifest:
// "<dtype>:<d0>,<d1>,...[=<relative raw file>]".
struct ArgSpec {
  DtypeSpec dt;
  std::vector<int64_t> dims;
  size_t total = 1;
  std::string file;  // empty = zeros
};

bool ParseArgSpec(const std::string& line, ArgSpec* out) {
  std::string spec = line;
  auto eq = spec.find('=');
  if (eq != std::string::npos) {
    out->file = spec.substr(eq + 1);
    spec = spec.substr(0, eq);
  }
  auto colon = spec.find(':');
  if (colon == std::string::npos || !ParseDtype(spec.substr(0, colon), &out->dt))
    return false;
  for (size_t pos = colon + 1; pos < spec.size();) {
    size_t next = spec.find(',', pos);
    if (next == std::string::npos) next = spec.size();
    out->dims.push_back(std::atoll(spec.substr(pos, next - pos).c_str()));
    out->total *= out->dims.back();
    pos = next + 1;
  }
  return true;
}

// The bundle's staging contract: every executable input in flatten order,
// plus which one is the image batch (the rank-4 u8 input) and its
// [batch, size] geometry — what serve/stage decode into.
struct Manifest {
  std::vector<ArgSpec> args;
  int image_arg = -1;
  int64_t batch = 0;
  int64_t size = 0;
};

bool LoadManifest(const std::string& bundle, Manifest* m) {
  FILE* f = std::fopen((bundle + "/args.txt").c_str(), "rb");
  if (!f) {
    std::fprintf(stderr, "pjrt_host: no args.txt in %s\n", bundle.c_str());
    return false;
  }
  char line[512];
  while (std::fgets(line, sizeof(line), f)) {
    std::string s(line);
    while (!s.empty() && (s.back() == '\n' || s.back() == '\r')) s.pop_back();
    if (s.empty() || s[0] == '#') continue;
    ArgSpec a;
    if (!ParseArgSpec(s, &a)) {
      std::fprintf(stderr, "pjrt_host: bad args.txt line: %s\n", s.c_str());
      std::fclose(f);
      return false;
    }
    if (a.dt.type == PJRT_Buffer_Type_U8 && a.dims.size() == 4 &&
        m->image_arg < 0) {
      m->image_arg = static_cast<int>(m->args.size());
      m->batch = a.dims[0];
      m->size = a.dims[1];
    }
    m->args.push_back(std::move(a));
  }
  std::fclose(f);
  return true;
}

// Boot the resident half: plugin + client + compiled executable + first
// addressable device + output count. Shared by run and serve — the
// load-once part of the reference's native member (services.rs:513-524).
struct Host {
  PJRT_Client* client = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  PJRT_Device* device = nullptr;
  size_t num_outputs = 0;
};

int Boot(const char* so_path, const char* options_path,
         const std::string& bundle, Host* h) {
  std::string default_opts = bundle + "/client_options.txt";
  Options opts;
  if (!options_path) {
    // The bundle's own options file is optional — but if it EXISTS and
    // fails to parse, abort loudly rather than handing the plugin an
    // empty option set and misdirecting debugging at it.
    FILE* probe = std::fopen(default_opts.c_str(), "rb");
    if (probe) {
      std::fclose(probe);
      options_path = default_opts.c_str();
    }
  }
  if (options_path && !LoadOptions(options_path, &opts)) return 1;

  std::string error;
  g_api = LoadApi(so_path, &error);
  if (!g_api) {
    std::fprintf(stderr, "pjrt_host: cannot load %s: %s\n", so_path, error.c_str());
    return 1;
  }
  PJRT_Plugin_Initialize_Args iargs;
  std::memset(&iargs, 0, sizeof(iargs));
  iargs.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  CHECK_PJRT(g_api->PJRT_Plugin_Initialize(&iargs));

  PJRT_Client_Create_Args cargs;
  std::memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = opts.values.data();
  cargs.num_options = opts.values.size();
  CHECK_PJRT(g_api->PJRT_Client_Create(&cargs));
  h->client = cargs.client;

  // Compile the StableHLO module with the Python-side-serialized options.
  std::string program_path = bundle + "/program.mlir";
  std::vector<char> program = ReadFile(program_path.c_str());
  std::vector<char> coptions = ReadFile((bundle + "/compile_options.pb").c_str());
  PJRT_Program prog;
  std::memset(&prog, 0, sizeof(prog));
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = program.data();
  prog.code_size = program.size();
  static const char kFormat[] = "mlir";
  prog.format = kFormat;
  prog.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args kargs;
  std::memset(&kargs, 0, sizeof(kargs));
  kargs.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  kargs.client = h->client;
  kargs.program = &prog;
  kargs.compile_options = coptions.data();
  kargs.compile_options_size = coptions.size();
  CHECK_PJRT(g_api->PJRT_Client_Compile(&kargs));
  h->exec = kargs.executable;
  std::fprintf(stderr, "pjrt_host: compiled %s (%zu bytes)\n",
               program_path.c_str(), program.size());

  PJRT_Client_AddressableDevices_Args aargs;
  std::memset(&aargs, 0, sizeof(aargs));
  aargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  aargs.client = h->client;
  CHECK_PJRT(g_api->PJRT_Client_AddressableDevices(&aargs));
  if (aargs.num_addressable_devices == 0) {
    std::fprintf(stderr, "pjrt_host: no addressable devices\n");
    return 1;
  }
  h->device = aargs.addressable_devices[0];

  PJRT_Executable_NumOutputs_Args noargs;
  std::memset(&noargs, 0, sizeof(noargs));
  noargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  {
    PJRT_LoadedExecutable_GetExecutable_Args geargs;
    std::memset(&geargs, 0, sizeof(geargs));
    geargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
    geargs.loaded_executable = h->exec;
    CHECK_PJRT(g_api->PJRT_LoadedExecutable_GetExecutable(&geargs));
    noargs.executable = geargs.executable;
    CHECK_PJRT(g_api->PJRT_Executable_NumOutputs(&noargs));
  }
  h->num_outputs = noargs.num_outputs;
  return 0;
}

void ShutdownHost(Host* h) {
  if (h->exec) {
    PJRT_LoadedExecutable_Destroy_Args ed;
    std::memset(&ed, 0, sizeof(ed));
    ed.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
    ed.executable = h->exec;
    g_api->PJRT_LoadedExecutable_Destroy(&ed);
  }
  if (h->client) {
    PJRT_Client_Destroy_Args cd;
    std::memset(&cd, 0, sizeof(cd));
    cd.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    cd.client = h->client;
    g_api->PJRT_Client_Destroy(&cd);
  }
}

// Stage one argument's host bytes onto the device. Returns null on failure
// (error already printed). The host data must stay valid until the
// returned buffer's done event fires; this helper awaits it, so callers
// may reuse `data` immediately.
PJRT_Buffer* StageBuffer(const Host& h, const ArgSpec& a, const void* data) {
  PJRT_Client_BufferFromHostBuffer_Args bargs;
  std::memset(&bargs, 0, sizeof(bargs));
  bargs.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  bargs.client = h.client;
  bargs.data = data;
  bargs.type = a.dt.type;
  bargs.dims = a.dims.data();
  bargs.num_dims = a.dims.size();
  bargs.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  bargs.device = h.device;
  PJRT_Error* err = g_api->PJRT_Client_BufferFromHostBuffer(&bargs);
  if (err) {
    std::fprintf(stderr, "pjrt_host: staging failed: %s\n", ErrMessage(err).c_str());
    return nullptr;
  }
  if (AwaitEvent(bargs.done_with_host_buffer)) {
    DestroyBuffer(bargs.buffer);
    return nullptr;
  }
  return bargs.buffer;
}

// Stage every manifest argument from its raw file (zeros when file-less).
// Returns nonzero on failure; fills `bufs` in manifest order.
int StageManifestArgs(const Host& h, const Manifest& m, const std::string& bundle,
                      std::vector<PJRT_Buffer*>* bufs) {
  for (const ArgSpec& a : m.args) {
    std::vector<char> input(a.total * a.dt.bytes, 0);
    if (!a.file.empty()) {
      std::string path = bundle + "/" + a.file;
      std::vector<char> raw = ReadFile(path.c_str());
      if (raw.size() != input.size()) {
        std::fprintf(stderr, "pjrt_host: %s is %zu bytes, want %zu\n",
                     path.c_str(), raw.size(), input.size());
        return 1;
      }
      input = std::move(raw);
    }
    PJRT_Buffer* b = StageBuffer(h, a, input.data());
    if (!b) return 1;
    bufs->push_back(b);
  }
  return 0;
}

int Run(int argc, char** argv) {
  const char* so_path = argv[2];
  std::string bundle = argv[3];
  const char* options_path = nullptr;
  int iters = 1;
  for (int i = 4; i + 1 < argc; i += 2) {
    if (std::strcmp(argv[i], "--options") == 0) options_path = argv[i + 1];
    else if (std::strcmp(argv[i], "--iters") == 0) iters = std::atoi(argv[i + 1]);
  }
  if (iters < 1) iters = 1;

  Manifest manifest;
  if (!LoadManifest(bundle, &manifest)) return 1;

  Host host;
  if (Boot(so_path, options_path, bundle, &host)) return 1;

  std::vector<PJRT_Buffer*> in_bufs;
  if (StageManifestArgs(host, manifest, bundle, &in_bufs)) return 1;
  size_t num_outputs = host.num_outputs;

  PJRT_ExecuteOptions eopts;
  std::memset(&eopts, 0, sizeof(eopts));
  eopts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_Buffer* const* arg_lists[1] = {in_bufs.data()};
  std::vector<PJRT_Buffer*> out_list(num_outputs, nullptr);
  PJRT_Event* first_ev = nullptr;
  CHECK_PJRT(DispatchExec(host.exec, &eopts, arg_lists, in_bufs.size(), &out_list, &first_ev));
  if (AwaitEvent(first_ev)) return 1;

  // Read back every output and report.
  std::printf("{\"outputs\": [");
  for (size_t i = 0; i < num_outputs; ++i) {
    std::vector<char> host_bytes;
    if (ReadbackBuffer(out_list[i], &host_bytes)) return 1;

    PJRT_Buffer_ElementType_Args etargs;
    std::memset(&etargs, 0, sizeof(etargs));
    etargs.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
    etargs.buffer = out_list[i];
    CHECK_PJRT(g_api->PJRT_Buffer_ElementType(&etargs));

    std::printf("%s{\"bytes\": %zu, \"type\": %d, \"head\": [", i ? ", " : "",
                host_bytes.size(), static_cast<int>(etargs.type));
    size_t shown = 0;
    if (etargs.type == PJRT_Buffer_Type_F32) {
      const float* f = reinterpret_cast<const float*>(host_bytes.data());
      for (; shown < 4 && shown < host_bytes.size() / 4; ++shown)
        std::printf("%s%g", shown ? ", " : "", f[shown]);
    } else if (etargs.type == PJRT_Buffer_Type_S32) {
      const int32_t* v = reinterpret_cast<const int32_t*>(host_bytes.data());
      for (; shown < 4 && shown < host_bytes.size() / 4; ++shown)
        std::printf("%s%d", shown ? ", " : "", v[shown]);
    }
    std::printf("]}");
    DestroyBuffer(out_list[i]);
  }
  std::printf("]}\n");

  if (iters > 1) {
    // Throughput: keep up to `depth` executions in flight (each Execute
    // allocates fresh output buffers, so dispatches don't alias), await
    // the oldest as new ones enter — the same pipelined-dispatch shape
    // the Python bench uses, measuring chip-side rate rather than one
    // round trip per step.
    const int depth = 8;
    std::vector<std::vector<PJRT_Buffer*>> pending_bufs;
    std::vector<PJRT_Event*> pending_events;
    auto await_oldest = [&]() -> int {
      if (AwaitEvent(pending_events.front())) return 1;
      pending_events.erase(pending_events.begin());
      DestroyBuffers(pending_bufs.front());
      pending_bufs.erase(pending_bufs.begin());
      return 0;
    };
    struct timespec t0, t1;
    clock_gettime(CLOCK_MONOTONIC, &t0);
    for (int i = 0; i < iters; ++i) {
      std::vector<PJRT_Buffer*> outs(num_outputs, nullptr);
      PJRT_Event* ev = nullptr;
      CHECK_PJRT(DispatchExec(host.exec, &eopts, arg_lists, in_bufs.size(), &outs, &ev));
      pending_bufs.push_back(std::move(outs));
      pending_events.push_back(ev);
      if (static_cast<int>(pending_events.size()) >= depth && await_oldest())
        return 1;
    }
    // Drain, then a FINAL execute whose output we read back to the host:
    // a plugin's completion events may resolve at dispatch-ack, so only a
    // host readback is a true end-of-work barrier.
    while (!pending_events.empty())
      if (await_oldest()) return 1;
    {
      std::vector<PJRT_Buffer*> outs(num_outputs, nullptr);
      PJRT_Event* ev = nullptr;
      CHECK_PJRT(DispatchExec(host.exec, &eopts, arg_lists, in_bufs.size(), &outs, &ev));
      if (AwaitEvent(ev)) return 1;
      std::vector<char> host_bytes;
      if (ReadbackBuffer(outs[0], &host_bytes)) return 1;
      DestroyBuffers(outs);
    }
    clock_gettime(CLOCK_MONOTONIC, &t1);
    int total_iters = iters + 1;  // incl. the readback-barrier execute
    double sec = (t1.tv_sec - t0.tv_sec) + (t1.tv_nsec - t0.tv_nsec) * 1e-9;
    std::printf("{\"iters\": %d, \"total_s\": %.4f, \"ms_per_exec\": %.3f}\n",
                total_iters, sec, sec * 1e3 / total_iters);
  }

  DestroyBuffers(in_bufs);
  ShutdownHost(&host);
  return 0;
}

// ---------------------------------------------------------------------------
// serve / stage: the resident JPEG->top-1 loop and its hermetic half
// ---------------------------------------------------------------------------

// Read one stdin request line of ANY length: fgets chunks are appended
// until the newline arrives, so a request longer than one buffer is never
// silently split into several bogus requests (each with a truncated path
// at the seam) answered by several reply lines. Returns false at EOF with
// nothing pending; a final unterminated line still counts as one request.
bool ReadRequestLine(std::string* line) {
  line->clear();
  char chunk[65536];
  while (std::fgets(chunk, sizeof(chunk), stdin)) {
    line->append(chunk);
    if (!line->empty() && line->back() == '\n') return true;
  }
  return !line->empty();
}

std::vector<std::string> SplitWhitespace(const std::string& line) {
  std::vector<std::string> out;
  size_t b = 0;
  while ((b = line.find_first_not_of(" \t\r\n", b)) != std::string::npos) {
    size_t e = line.find_first_of(" \t\r\n", b);
    if (e == std::string::npos) e = line.size();
    out.push_back(line.substr(b, e - b));
    b = e;
  }
  return out;
}

// Hermetic self-test of the request framing (no plugin, no TPU): echo one
// JSON line per stdin request with its token count. A CPU-only test pipes
// a request far longer than the fgets buffer through this and asserts ONE
// reply — the line-framed request/response contract serve relies on.
int FrameCheck() {
  std::string line;
  while (ReadRequestLine(&line)) {
    std::vector<std::string> toks = SplitWhitespace(line);
    if (toks.empty()) continue;
    std::printf("{\"paths\": %zu, \"bytes\": %zu}\n", toks.size(), line.size());
  }
  std::fflush(stdout);
  return 0;
}

bool HasJpegSuffix(const std::string& name) {
  auto dot = name.rfind('.');
  if (dot == std::string::npos) return false;
  std::string ext = name.substr(dot + 1);
  std::transform(ext.begin(), ext.end(), ext.begin(),
                 [](unsigned char c) { return std::tolower(c); });
  return ext == "jpg" || ext == "jpeg";
}

std::vector<std::string> ListJpegs(const std::string& dir) {
  std::vector<std::string> out;
  DIR* d = opendir(dir.c_str());
  if (!d) {
    std::fprintf(stderr, "pjrt_host: cannot open dir %s\n", dir.c_str());
    return out;
  }
  while (dirent* e = readdir(d)) {
    std::string name = e->d_name;
    if (HasJpegSuffix(name)) out.push_back(dir + "/" + name);
  }
  closedir(d);
  std::sort(out.begin(), out.end());
  return out;
}

// Decode up to `batch` paths into out[batch, size, size, 3] u8, padding by
// repetition (the exporter's contract: pjrt_bundle.py pads with np.tile).
// Returns the number of decode FAILURES among the real (unpadded) slots;
// `failed` (optional) receives the per-real-slot failure flags so replies
// can mark the affected entries instead of presenting zero-image results
// as confident predictions.
int DecodePadded(const std::vector<std::string>& paths, int64_t batch,
                 int64_t size, uint8_t* out, int threads,
                 std::vector<bool>* failed = nullptr) {
  std::vector<const char*> cpaths(batch);
  for (int64_t i = 0; i < batch; ++i)
    cpaths[i] = paths[i % paths.size()].c_str();
  std::vector<int> status(batch, 0);
  dmlc_decode_resize_batch(cpaths.data(), static_cast<int>(batch),
                           static_cast<int>(size), out, status.data(), threads);
  int failures = 0;
  if (failed) failed->assign(paths.size(), false);
  for (size_t i = 0; i < paths.size() && i < static_cast<size_t>(batch); ++i) {
    if (status[i] != 0) {
      ++failures;
      if (failed) (*failed)[i] = true;
    }
  }
  return failures;
}

// Execute one staged image batch against the resident weights and read the
// (top-1 index, top-1 prob) outputs back. Returns nonzero on failure.
int ClassifyStaged(const Host& h, const Manifest& m,
                   std::vector<PJRT_Buffer*>& args, PJRT_Buffer* image,
                   std::vector<int32_t>* top1, std::vector<float>* prob) {
  args[m.image_arg] = image;
  PJRT_ExecuteOptions eopts;
  std::memset(&eopts, 0, sizeof(eopts));
  eopts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
  PJRT_Buffer* const* arg_lists[1] = {args.data()};
  std::vector<PJRT_Buffer*> outs(h.num_outputs, nullptr);
  PJRT_Event* ev = nullptr;
  // Every early return must destroy whatever outs filled in (AwaitEvent and
  // ReadbackBuffer already destroy their events): serve treats these
  // failures as fatal today, but a caller that keeps going must not leak a
  // batch of output buffers per failed execute.
  auto fail = [&outs]() {
    DestroyBuffers(outs);
    return 1;
  };
  PJRT_Error* err = DispatchExec(h.exec, &eopts, arg_lists, args.size(), &outs, &ev);
  if (err) {
    std::fprintf(stderr, "pjrt_host: execute failed: %s\n", ErrMessage(err).c_str());
    return fail();
  }
  if (AwaitEvent(ev)) return fail();
  std::vector<char> idx_bytes, prob_bytes;
  if (ReadbackBuffer(outs[0], &idx_bytes)) return fail();
  if (outs.size() > 1 && ReadbackBuffer(outs[1], &prob_bytes)) return fail();
  DestroyBuffers(outs);
  top1->assign(reinterpret_cast<const int32_t*>(idx_bytes.data()),
               reinterpret_cast<const int32_t*>(idx_bytes.data() + idx_bytes.size()));
  prob->assign(reinterpret_cast<const float*>(prob_bytes.data()),
               reinterpret_cast<const float*>(prob_bytes.data() + prob_bytes.size()));
  return 0;
}

void PrintBatchResult(const std::vector<std::string>& files,
                      const std::vector<int32_t>& top1,
                      const std::vector<float>& prob,
                      const std::vector<bool>& decode_failed) {
  std::printf("{\"files\": [");
  for (size_t i = 0; i < files.size(); ++i) {
    auto slash = files[i].rfind('/');
    std::string base = slash == std::string::npos ? files[i] : files[i].substr(slash + 1);
    std::printf("%s\"%s\"", i ? ", " : "", JsonEscape(base).c_str());
  }
  std::printf("], \"top1\": [");
  for (size_t i = 0; i < files.size() && i < top1.size(); ++i)
    std::printf("%s%d", i ? ", " : "", top1[i]);
  std::printf("], \"prob\": [");
  for (size_t i = 0; i < files.size() && i < prob.size(); ++i)
    std::printf("%s%.6g", i ? ", " : "", prob[i]);
  std::printf("]");
  // In-protocol failure marker: a zero-filled slot's "prediction" must not
  // read as a confident answer to a stdout consumer (stderr notes are not
  // part of the reply).
  bool any = false;
  for (bool f : decode_failed) any |= f;
  if (any) {
    std::printf(", \"decode_failed\": [");
    bool first = true;
    for (size_t i = 0; i < decode_failed.size(); ++i) {
      if (!decode_failed[i]) continue;
      std::printf("%s%zu", first ? "" : ", ", i);
      first = false;
    }
    std::printf("]");
  }
  std::printf("}\n");
  std::fflush(stdout);
}

// The hermetic half of serve: decode --dir into the manifest's image-arg
// layout and write the raw bytes serve would stage. No plugin, no TPU — a
// CPU-only test diffs this against the Python pipeline byte for byte.
int Stage(int argc, char** argv) {
  std::string bundle = argv[2];
  const char* dir = nullptr;
  const char* out_path = nullptr;
  int threads = 0;
  for (int i = 3; i + 1 < argc; i += 2) {
    if (std::strcmp(argv[i], "--dir") == 0) dir = argv[i + 1];
    else if (std::strcmp(argv[i], "--out") == 0) out_path = argv[i + 1];
    else if (std::strcmp(argv[i], "--threads") == 0) threads = std::atoi(argv[i + 1]);
  }
  if (!dir || !out_path) {
    std::fprintf(stderr, "pjrt_host: stage needs --dir and --out\n");
    return 2;
  }
  Manifest m;
  if (!LoadManifest(bundle, &m)) return 1;
  if (m.image_arg < 0) {
    std::fprintf(stderr, "pjrt_host: manifest has no u8 image input\n");
    return 1;
  }
  std::vector<std::string> files = ListJpegs(dir);
  if (files.empty()) {
    std::fprintf(stderr, "pjrt_host: no JPEGs in %s\n", dir);
    return 1;
  }
  if (static_cast<int64_t>(files.size()) > m.batch) files.resize(m.batch);
  std::vector<uint8_t> staged(m.batch * m.size * m.size * 3);
  int failures = DecodePadded(files, m.batch, m.size, staged.data(), threads);
  FILE* f = std::fopen(out_path, "wb");
  if (!f || std::fwrite(staged.data(), 1, staged.size(), f) != staged.size()) {
    std::fprintf(stderr, "pjrt_host: cannot write %s\n", out_path);
    if (f) std::fclose(f);
    return 1;
  }
  std::fclose(f);
  std::printf(
      "{\"batch\": %lld, \"size\": %lld, \"files\": %zu, \"padded\": %lld, "
      "\"decode_failures\": %d, \"bytes\": %zu}\n",
      static_cast<long long>(m.batch), static_cast<long long>(m.size),
      files.size(), static_cast<long long>(m.batch) - static_cast<long long>(files.size()),
      failures, staged.size());
  return failures ? 1 : 0;
}

// The resident serving loop (reference: services.rs:475-497 — load once,
// answer predict forever). Boot + compile + stage weights ONCE; then:
//   1. --dir: classify every JPEG under it, one JSON line per batch;
//   2. --repeat N: N pipelined passes over the dir measuring the sustained
//      native JPEG->top-1 rate (decode of batch k+1 overlaps execution of
//      batch k — the serve-side analog of run's --iters pipeline);
//   3. stdin: one request per line (whitespace-separated JPEG paths),
//      answered with a JSON result line, until EOF.
int Serve(int argc, char** argv) {
  const char* so_path = argv[2];
  std::string bundle = argv[3];
  const char* options_path = nullptr;
  const char* dir = nullptr;
  int repeat = 0;
  int threads = 0;
  for (int i = 4; i + 1 < argc; i += 2) {
    if (std::strcmp(argv[i], "--options") == 0) options_path = argv[i + 1];
    else if (std::strcmp(argv[i], "--dir") == 0) dir = argv[i + 1];
    else if (std::strcmp(argv[i], "--repeat") == 0) repeat = std::atoi(argv[i + 1]);
    else if (std::strcmp(argv[i], "--threads") == 0) threads = std::atoi(argv[i + 1]);
  }

  if (repeat > 0 && !dir) {
    std::fprintf(stderr,
                 "pjrt_host: --repeat needs --dir (nothing to measure); "
                 "refusing to fall through to the stdin loop\n");
    return 2;
  }

  Manifest manifest;
  if (!LoadManifest(bundle, &manifest)) return 1;
  if (manifest.image_arg < 0) {
    std::fprintf(stderr, "pjrt_host: manifest has no u8 image input to serve\n");
    return 1;
  }
  const int64_t B = manifest.batch, S = manifest.size;
  if (B <= 0 || S <= 0) {
    // A zero batch would make every chunk loop below spin forever.
    std::fprintf(stderr,
                 "pjrt_host: degenerate image geometry batch=%lld size=%lld\n",
                 static_cast<long long>(B), static_cast<long long>(S));
    return 1;
  }

  Host host;
  if (Boot(so_path, options_path, bundle, &host)) return 1;

  // Stage every argument once; the image slot's boot-time buffer (zeros or
  // the export-time image.raw) is replaced per request.
  std::vector<PJRT_Buffer*> args;
  if (StageManifestArgs(host, manifest, bundle, &args)) return 1;
  PJRT_Buffer* boot_image = args[manifest.image_arg];
  std::fprintf(stderr,
               "pjrt_host: serving batch=%lld size=%lld (weights resident, "
               "native decode in-process)\n",
               static_cast<long long>(B), static_cast<long long>(S));

  std::vector<uint8_t> pixels(B * S * S * 3);
  // The ONE chunk iterator every phase uses: batch-sized sub-lists of
  // `paths`, the callback returning nonzero to abort.
  auto for_each_chunk = [B](const std::vector<std::string>& paths,
                            auto fn) -> int {
    for (size_t s = 0; s < paths.size(); s += B) {
      std::vector<std::string> chunk(
          paths.begin() + s,
          paths.begin() + std::min(paths.size(), s + static_cast<size_t>(B)));
      if (int rc = fn(chunk)) return rc;
    }
    return 0;
  };
  // Classify one <=B chunk against the resident weights, APPENDING the
  // per-real-slot results — callers aggregate chunks into one reply.
  auto classify_chunk = [&](const std::vector<std::string>& chunk,
                            std::vector<int32_t>* top1, std::vector<float>* prob,
                            std::vector<bool>* failed) -> int {
    std::vector<bool> decode_failed;
    int failures = DecodePadded(chunk, B, S, pixels.data(), threads, &decode_failed);
    if (failures)
      std::fprintf(stderr, "pjrt_host: %d decode failure(s) in batch\n", failures);
    PJRT_Buffer* image = StageBuffer(host, manifest.args[manifest.image_arg],
                                     pixels.data());
    if (!image) return 1;
    std::vector<int32_t> t;
    std::vector<float> p;
    int rc = ClassifyStaged(host, manifest, args, image, &t, &p);
    DestroyBuffer(image);
    if (rc) return rc;
    for (size_t i = 0; i < chunk.size(); ++i) {
      top1->push_back(i < t.size() ? t[i] : -1);
      prob->push_back(i < p.size() ? p[i] : 0.0f);
      failed->push_back(decode_failed[i]);
    }
    return 0;
  };
  // One request (any size) -> ONE JSON reply line, chunked internally:
  // stdin clients frame responses by line, so a 130-image request against
  // a batch-64 bundle must not answer as three lines.
  auto classify_request = [&](const std::vector<std::string>& paths) -> int {
    std::vector<int32_t> top1;
    std::vector<float> prob;
    std::vector<bool> failed;
    int rc = for_each_chunk(paths, [&](const std::vector<std::string>& chunk) {
      return classify_chunk(chunk, &top1, &prob, &failed);
    });
    if (rc) return rc;
    PrintBatchResult(paths, top1, prob, failed);
    return 0;
  };

  // Phase 1: classify the directory, one reply line per batch (streaming —
  // a large directory should not buffer its whole answer).
  std::vector<std::string> files;
  if (dir) {
    files = ListJpegs(dir);
    if (files.empty()) {
      std::fprintf(stderr, "pjrt_host: no JPEGs in %s\n", dir);
      return 1;
    }
    if (for_each_chunk(files, [&](const std::vector<std::string>& chunk) {
          return classify_request(chunk);
        }))
      return 1;
  }

  // Phase 2: sustained-throughput passes, decode pipelined against device
  // execution. Results are NOT read back per batch (a host round trip
  // per batch would serialize the pipeline); the final batch IS read back as
  // the true end-of-work barrier, exactly like run's --iters mode.
  if (dir && repeat > 0) {
    const size_t depth = 2;
    std::vector<PJRT_Buffer*> pending_images;
    std::vector<std::vector<PJRT_Buffer*>> pending_outs;
    std::vector<PJRT_Event*> pending_events;
    auto await_oldest = [&]() -> int {
      if (AwaitEvent(pending_events.front())) return 1;
      pending_events.erase(pending_events.begin());
      DestroyBuffers(pending_outs.front());
      pending_outs.erase(pending_outs.begin());
      DestroyBuffer(pending_images.front());
      pending_images.erase(pending_images.begin());
      return 0;
    };
    PJRT_ExecuteOptions eopts;
    std::memset(&eopts, 0, sizeof(eopts));
    eopts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
    long long images = 0;
    long long decode_failures = 0;
    struct timespec t0, t1;
    clock_gettime(CLOCK_MONOTONIC, &t0);
    for (int pass = 0; pass < repeat; ++pass) {
      int rc = for_each_chunk(files, [&](const std::vector<std::string>& chunk) {
        // Decode on the host WHILE the previously dispatched batches run.
        decode_failures += DecodePadded(chunk, B, S, pixels.data(), threads);
        PJRT_Buffer* image =
            StageBuffer(host, manifest.args[manifest.image_arg], pixels.data());
        if (!image) return 1;
        args[manifest.image_arg] = image;
        PJRT_Buffer* const* arg_lists[1] = {args.data()};
        std::vector<PJRT_Buffer*> outs(host.num_outputs, nullptr);
        PJRT_Event* ev = nullptr;
        PJRT_Error* err =
            DispatchExec(host.exec, &eopts, arg_lists, args.size(), &outs, &ev);
        if (err) {
          std::fprintf(stderr, "pjrt_host: execute failed: %s\n",
                       ErrMessage(err).c_str());
          return 1;
        }
        pending_images.push_back(image);
        pending_outs.push_back(std::move(outs));
        pending_events.push_back(ev);
        images += chunk.size();
        if (pending_events.size() >= depth && await_oldest()) return 1;
        return 0;
      });
      if (rc) return 1;
    }
    // Drain all but the last; read the last batch's top-1 back as the
    // barrier that proves the work actually finished on-device.
    while (pending_events.size() > 1)
      if (await_oldest()) return 1;
    if (!pending_events.empty()) {
      if (AwaitEvent(pending_events.front())) return 1;
      std::vector<char> barrier;
      if (ReadbackBuffer(pending_outs.front()[0], &barrier)) return 1;
      DestroyBuffers(pending_outs.front());
      DestroyBuffer(pending_images.front());
      pending_events.clear();
      pending_outs.clear();
      pending_images.clear();
    }
    clock_gettime(CLOCK_MONOTONIC, &t1);
    double sec = (t1.tv_sec - t0.tv_sec) + (t1.tv_nsec - t0.tv_nsec) * 1e-9;
    // decode_failures keeps the rate honest: a zero-filled slot was
    // classified but was not a successful JPEG->top-1 (stage exits 1 on
    // failures; this reports them in-protocol instead).
    std::printf(
        "{\"images\": %lld, \"total_s\": %.4f, \"jpeg_to_top1_img_s\": %.1f, "
        "\"batch\": %lld, \"passes\": %d, \"decode_failures\": %lld}\n",
        images, sec, images / sec, static_cast<long long>(B), repeat,
        decode_failures);
    std::fflush(stdout);
  }

  // Phase 3: the long-lived request loop. One line = one predict request
  // (whitespace-separated JPEG paths — ANY count; oversized requests are
  // chunked internally but always answered as ONE JSON line, preserving
  // the line-framed request/response contract); EOF ends the process.
  // This is the reference's `predict` service surface
  // (services.rs:475-497) with the model resident from boot.
  // One physical line = one request, at ANY length (ReadRequestLine
  // accumulates past the fgets buffer; frame-check pins this hermetically).
  std::string line;
  while (ReadRequestLine(&line)) {
    std::vector<std::string> paths = SplitWhitespace(line);
    if (paths.empty()) continue;
    if (classify_request(paths)) {
      // A failed execute is fatal (client state unknown); a decode
      // failure was already reported per-slot and the request answered.
      return 1;
    }
  }

  args[manifest.image_arg] = boot_image;
  DestroyBuffers(args);
  ShutdownHost(&host);
  return 0;
}

}  // namespace

int main(int argc, char** argv) {
  if (argc >= 3 && std::strcmp(argv[1], "probe") == 0)
    return Probe(argv[2], argc > 3 ? argv[3] : nullptr);
  if (argc >= 4 && std::strcmp(argv[1], "run") == 0) return Run(argc, argv);
  if (argc >= 4 && std::strcmp(argv[1], "serve") == 0) return Serve(argc, argv);
  if (argc >= 3 && std::strcmp(argv[1], "stage") == 0) return Stage(argc, argv);
  if (argc >= 2 && std::strcmp(argv[1], "frame-check") == 0) return FrameCheck();
  std::fprintf(stderr,
               "usage:\n"
               "  pjrt_host probe <plugin.so> [client_options.txt]\n"
               "  pjrt_host run <plugin.so> <bundle_dir> [--options f] [--iters N]\n"
               "  pjrt_host serve <plugin.so> <bundle_dir> [--options f] [--dir d]\n"
               "                  [--repeat N] [--threads N]\n"
               "    resident loop: --dir classified batch-wise, --repeat N timed\n"
               "    pipelined passes, then one predict request per stdin line\n"
               "  pjrt_host stage <bundle_dir> --dir d --out staged.raw\n"
               "    hermetic: decode into the manifest's image layout, no TPU\n"
               "    bundle: program.mlir + compile_options.pb + args.txt manifest\n"
               "  pjrt_host frame-check\n"
               "    hermetic: echo serve's stdin request framing (tests)\n");
  return 2;
}
