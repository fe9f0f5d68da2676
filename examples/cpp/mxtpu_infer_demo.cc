// Native (C++) TPU inference through the PJRT C API — the reference's
// C predict API (src/c_api/c_predict_api.cc MXPredCreate/MXPredForward)
// redone TPU-first (round 5, VERDICT item 4): a non-Python consumer
//
//   1. loads a gluon checkpoint through libmxtpu_io.so's C ABI,
//   2. loads the exported StableHLO graph + serialized CompileOptions
//      (written by mx.onnx.export_for_pjrt_c),
//   3. creates the PJRT client ($MXTPU_PJRT_SO, default libtpu.so),
//      compiles the module,
//   4. stages param + data buffers, executes ON THE TPU,
//   5. writes the outputs back as a .params file Python can load.
//
// No Python anywhere. Build: make -C examples/cpp mxtpu_infer_demo
// Run:  mxtpu_infer_demo <export-prefix> <input.params> <output.params>
//
// NOTE: this file deliberately spells out every raw PJRT/manifest call
// — it is the "what the C ABI + PJRT C API actually look like"
// reference. Application code should use the header-only frontend
// instead (include/mxtpu_cpp.hpp, consumed by mxtpu_cpp_demo.cc),
// which wraps the same sequence with RAII and error handling.
//       (input.params holds one entry per manifest `input data j`,
//        named "0", "1", ...; outputs land as "0", "1", ...)

#include <dlfcn.h>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

extern "C" {  // libmxtpu_io.so checkpoint ABI
void* mxio_params_open(const char* path);
int mxio_params_count(void* h);
const char* mxio_params_name(void* h, int i);
int mxio_params_info(void* h, int i, int* dtype, int64_t* shape,
                     int max_ndim, int64_t* nbytes);
int64_t mxio_params_read(void* h, int i, void* out, int64_t cap);
void mxio_params_close(void* h);
void* mxio_params_writer_open(const char* path);
int mxio_params_writer_add(void* h, const char* name, int dtype, int ndim,
                           const int64_t* shape, const void* data);
int mxio_params_writer_close(void* h);
}

namespace {

// reference TypeFlag code -> PJRT element type (+ element size)
PJRT_Buffer_Type ToPjrtType(int tf) {
  switch (tf) {
    case 0: return PJRT_Buffer_Type_F32;
    case 1: return PJRT_Buffer_Type_F64;
    case 2: return PJRT_Buffer_Type_F16;
    case 3: return PJRT_Buffer_Type_U8;
    case 4: return PJRT_Buffer_Type_S32;
    case 5: return PJRT_Buffer_Type_S8;
    case 6: return PJRT_Buffer_Type_S64;
    case 12: return PJRT_Buffer_Type_BF16;
    default: return PJRT_Buffer_Type_INVALID;
  }
}
int TypeSize(int tf) {
  switch (tf) {
    case 0: case 4: return 4;
    case 1: case 6: return 8;
    case 2: case 12: return 2;
    default: return 1;
  }
}

struct Input {
  bool is_param;
  std::string key;       // checkpoint key or data index
  int dtype;
  std::vector<int64_t> dims;
};

const PJRT_Api* g_api = nullptr;

bool Check(PJRT_Error* err, const char* what) {
  if (!err) return true;
  PJRT_Error_Message_Args em;
  std::memset(&em, 0, sizeof em);
  em.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  em.error = err;
  g_api->PJRT_Error_Message(&em);
  std::fprintf(stderr, "%s: %.*s\n", what,
               static_cast<int>(em.message_size), em.message);
  PJRT_Error_Destroy_Args ed;
  std::memset(&ed, 0, sizeof ed);
  ed.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  ed.error = err;
  g_api->PJRT_Error_Destroy(&ed);
  return false;
}

bool Await(PJRT_Event* ev, const char* what) {
  PJRT_Event_Await_Args aw;
  std::memset(&aw, 0, sizeof aw);
  aw.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aw.event = ev;
  bool ok = Check(g_api->PJRT_Event_Await(&aw), what);
  PJRT_Event_Destroy_Args ed;
  std::memset(&ed, 0, sizeof ed);
  ed.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  ed.event = ev;
  g_api->PJRT_Event_Destroy(&ed);
  return ok;
}

std::string ReadFile(const char* path) {
  FILE* f = std::fopen(path, "rb");
  if (!f) return {};
  std::fseek(f, 0, SEEK_END);
  long n = std::ftell(f);
  std::fseek(f, 0, SEEK_SET);
  std::string s(static_cast<size_t>(n), '\0');
  if (n && std::fread(&s[0], 1, s.size(), f) != s.size()) s.clear();
  std::fclose(f);
  return s;
}

}  // namespace

int main(int argc, char** argv) {
  if (argc < 4) {
    std::fprintf(stderr,
                 "usage: %s <export-prefix> <input.params> <out.params>\n",
                 argv[0]);
    return 2;
  }
  const std::string prefix = argv[1];

  // ---- manifest ----------------------------------------------------------
  std::string mf = ReadFile((prefix + ".manifest").c_str());
  if (mf.rfind("mxtpu-pjrt v1", 0) != 0) {
    std::fprintf(stderr, "bad manifest\n");
    return 1;
  }
  std::vector<Input> inputs;
  std::vector<Input> outputs;
  {
    const char* p = mf.c_str();
    char kind[16], sub[16], key[512];
    while ((p = std::strchr(p, '\n'))) {
      ++p;
      int dtype, ndim, off = 0;
      if (std::sscanf(p, "input %15s %511s %d %d%n", sub, key, &dtype,
                      &ndim, &off) == 4) {
        Input in{std::strcmp(sub, "param") == 0, key, dtype, {}};
        const char* q = p + off;
        for (int d = 0; d < ndim; ++d) {
          long long v;
          int o2 = 0;
          if (std::sscanf(q, " %lld%n", &v, &o2) != 1) return 1;
          in.dims.push_back(v);
          q += o2;
        }
        inputs.push_back(std::move(in));
      } else if (std::sscanf(p, "output %15s %d %d%n", key, &dtype, &ndim,
                             &off) == 3) {
        Input out{false, key, dtype, {}};
        const char* q = p + off;
        for (int d = 0; d < ndim; ++d) {
          long long v;
          int o2 = 0;
          if (std::sscanf(q, " %lld%n", &v, &o2) != 1) return 1;
          out.dims.push_back(v);
          q += o2;
        }
        outputs.push_back(std::move(out));
      }
      (void)kind;
    }
  }
  std::printf("manifest: %zu inputs, %zu outputs\n", inputs.size(),
              outputs.size());

  // ---- host-side tensors (checkpoint + user input via the C ABI) ---------
  auto load_all = [](const char* path) {
    std::vector<std::pair<std::string, std::vector<uint8_t>>> out;
    void* h = mxio_params_open(path);
    if (!h) return out;
    for (int i = 0; i < mxio_params_count(h); ++i) {
      int dt;
      int64_t shape[32], nb;
      if (mxio_params_info(h, i, &dt, shape, 32, &nb) < 0) continue;
      std::vector<uint8_t> buf(static_cast<size_t>(nb));
      if (mxio_params_read(h, i, buf.data(), nb) != nb) continue;
      out.emplace_back(mxio_params_name(h, i), std::move(buf));
    }
    mxio_params_close(h);
    return out;
  };
  auto params = load_all((prefix + ".params").c_str());
  auto data_in = load_all(argv[2]);
  auto find = [](decltype(params)& v, const std::string& k)
      -> std::vector<uint8_t>* {
    for (auto& kv : v)
      if (kv.first == k) return &kv.second;
    return nullptr;
  };

  // ---- PJRT client -------------------------------------------------------
  const char* so_path = std::getenv("MXTPU_PJRT_SO");
  void* so = dlopen(so_path ? so_path : "libtpu.so", RTLD_NOW | RTLD_GLOBAL);
  if (!so) {
    std::fprintf(stderr, "dlopen PJRT library: %s\n", dlerror());
    return 1;
  }
  typedef const PJRT_Api* (*GetApiFn)(void);
  GetApiFn get_api = reinterpret_cast<GetApiFn>(dlsym(so, "GetPjrtApi"));
  if (!get_api) {
    std::fprintf(stderr, "GetPjrtApi not exported: %s\n", dlerror());
    return 1;
  }
  g_api = get_api();
  std::printf("PJRT api %d.%d\n", g_api->pjrt_api_version.major_version,
              g_api->pjrt_api_version.minor_version);

  // one-time plugin setup: libtpu refuses every other call before it
  PJRT_Plugin_Initialize_Args pi;
  std::memset(&pi, 0, sizeof pi);
  pi.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  if (!Check(g_api->PJRT_Plugin_Initialize(&pi), "plugin initialize"))
    return 1;

  PJRT_Client_Create_Args cc;
  std::memset(&cc, 0, sizeof cc);
  cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  if (!Check(g_api->PJRT_Client_Create(&cc), "client create")) return 1;

  PJRT_Client_AddressableDevices_Args ad;
  std::memset(&ad, 0, sizeof ad);
  ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  ad.client = cc.client;
  if (!Check(g_api->PJRT_Client_AddressableDevices(&ad), "devices") ||
      ad.num_addressable_devices == 0)
    return 1;
  PJRT_Device* dev = ad.addressable_devices[0];

  // ---- compile the StableHLO module --------------------------------------
  std::string code = ReadFile((prefix + ".stablehlo").c_str());
  std::string copts = ReadFile((prefix + ".copts").c_str());
  if (code.empty() || copts.empty()) {
    std::fprintf(stderr, "missing .stablehlo/.copts\n");
    return 1;
  }
  PJRT_Program prog;
  std::memset(&prog, 0, sizeof prog);
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = &code[0];
  prog.code_size = code.size();
  static const char kFmt[] = "mlir";
  prog.format = kFmt;
  prog.format_size = sizeof(kFmt) - 1;
  PJRT_Client_Compile_Args co;
  std::memset(&co, 0, sizeof co);
  co.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  co.client = cc.client;
  co.program = &prog;
  co.compile_options = copts.data();
  co.compile_options_size = copts.size();
  if (!Check(g_api->PJRT_Client_Compile(&co), "compile")) return 1;
  std::printf("compiled %zu-byte StableHLO module\n", code.size());

  // ---- stage input buffers ------------------------------------------------
  std::vector<PJRT_Buffer*> bufs;
  for (const auto& in : inputs) {
    std::vector<uint8_t>* host =
        in.is_param ? find(params, in.key) : find(data_in, in.key);
    if (!host) {
      std::fprintf(stderr, "missing tensor %s\n", in.key.c_str());
      return 1;
    }
    int64_t want = TypeSize(in.dtype);
    for (int64_t d : in.dims) want *= d;
    if (static_cast<int64_t>(host->size()) != want) {
      std::fprintf(stderr, "%s: %zu bytes, manifest wants %lld\n",
                   in.key.c_str(), host->size(),
                   static_cast<long long>(want));
      return 1;
    }
    PJRT_Client_BufferFromHostBuffer_Args bh;
    std::memset(&bh, 0, sizeof bh);
    bh.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    bh.client = cc.client;
    bh.data = host->data();
    bh.type = ToPjrtType(in.dtype);
    bh.dims = in.dims.data();
    bh.num_dims = in.dims.size();
    bh.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    bh.device = dev;
    if (!Check(g_api->PJRT_Client_BufferFromHostBuffer(&bh), "h2d"))
      return 1;
    if (!Await(bh.done_with_host_buffer, "h2d done")) return 1;
    bufs.push_back(bh.buffer);
  }

  // ---- execute ------------------------------------------------------------
  PJRT_ExecuteOptions eo;
  std::memset(&eo, 0, sizeof eo);
  eo.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
  PJRT_Buffer** arg_list = bufs.data();
  std::vector<PJRT_Buffer*> out_bufs(outputs.size());
  PJRT_Buffer** out_list = out_bufs.data();
  PJRT_LoadedExecutable_Execute_Args ex;
  std::memset(&ex, 0, sizeof ex);
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.executable = co.executable;
  ex.options = &eo;
  ex.num_devices = 1;
  ex.num_args = bufs.size();
  ex.argument_lists = &arg_list;
  ex.output_lists = &out_list;
  if (!Check(g_api->PJRT_LoadedExecutable_Execute(&ex), "execute"))
    return 1;
  std::printf("executed on TPU\n");

  // ---- fetch outputs + write them as .params ------------------------------
  void* w = mxio_params_writer_open(argv[3]);
  if (!w) return 1;
  int rc = 0;
  for (size_t i = 0; i < outputs.size(); ++i) {
    int64_t nbytes = TypeSize(outputs[i].dtype);
    for (int64_t d : outputs[i].dims) nbytes *= d;
    std::vector<uint8_t> host(static_cast<size_t>(nbytes));
    PJRT_Buffer_ToHostBuffer_Args th;
    std::memset(&th, 0, sizeof th);
    th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    th.src = out_bufs[i];
    th.dst = host.data();
    th.dst_size = host.size();
    if (!Check(g_api->PJRT_Buffer_ToHostBuffer(&th), "d2h")) {
      rc = 1;
      break;
    }
    if (!Await(th.event, "d2h done")) {
      rc = 1;
      break;
    }
    if (mxio_params_writer_add(w, outputs[i].key.c_str(),
                               outputs[i].dtype,
                               static_cast<int>(outputs[i].dims.size()),
                               outputs[i].dims.data(),
                               host.data()) != 0)
      rc = 1;
  }
  if (mxio_params_writer_close(w) != 0) rc = 1;
  std::printf(rc == 0 ? "wrote %s\n" : "FAILED\n", argv[3]);
  return rc;
}
