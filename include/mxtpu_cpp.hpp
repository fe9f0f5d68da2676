// mxtpu_cpp.hpp — header-only C++ frontend over the framework's C ABI.
//
// The reference ships cpp-package/ (header-only NDArray/Symbol/Module
// classes over the libmxnet C API) so C++ programs can run models
// without Python. This is the TPU-native equivalent, deployment-
// focused: Tensor + Checkpoint (.params read/write), RecordIO
// reader/writer, and a PJRT Predictor that compiles an exported
// StableHLO graph and executes inference on the TPU — the
// MXPredCreate/MXPredForward story (src/c_api/c_predict_api.cc),
// re-designed for the PJRT runtime.
//
// Link against libmxtpu_io.so; the Predictor additionally dlopens the
// PJRT library named by $MXTPU_PJRT_SO (default: libtpu.so, as the
// installed libtpu package ships it) at construction. Requires the
// PJRT C API header on the include path (see examples/cpp/Makefile).
//
// Usage (see examples/cpp/mxtpu_cpp_demo.cc):
//
//   auto ckpt = mxtpu::cpp::Checkpoint::Load("net.params");
//   mxtpu::cpp::Predictor pred("net", "net.params");   // export prefix
//   auto out = pred.Forward({input_tensor});
//   mxtpu::cpp::Checkpoint::Save("out.params", {{"0", out[0]}});

#pragma once

#include <cstdint>
#include <cstring>
#include <map>
#include <memory>
#include <stdexcept>
#include <string>
#include <vector>

extern "C" {
void* mxio_params_open(const char* path);
int mxio_params_count(void* h);
const char* mxio_params_name(void* h, int i);
const char* mxio_params_descr(void* h, int i);
int mxio_params_info(void* h, int i, int* dtype, int64_t* shape,
                     int max_ndim, int64_t* nbytes);
int64_t mxio_params_read(void* h, int i, void* out, int64_t cap);
void mxio_params_close(void* h);
void* mxio_params_writer_open(const char* path);
int mxio_params_writer_add(void* h, const char* name, int dtype, int ndim,
                           const int64_t* shape, const void* data);
int mxio_params_writer_close(void* h);
void* mxio_reader_open(const char* path, int prefetch);
int mxio_reader_next(void* h, const uint8_t** data, size_t* len);
void mxio_reader_reset(void* h);
void mxio_reader_close(void* h);
void* mxio_recwriter_open(const char* path);
int mxio_recwriter_write(void* h, const uint8_t* data, size_t len);
int mxio_recwriter_close(void* h);
}

namespace mxtpu {
namespace cpp {

// reference mshadow TypeFlag codes (the C ABI's dtype convention);
// kBfloat16 is 12, matching the reference enum (7 there is kBool)
enum class DType : int {
  kFloat32 = 0, kFloat64 = 1, kFloat16 = 2, kUint8 = 3,
  kInt32 = 4, kInt8 = 5, kInt64 = 6, kBfloat16 = 12,
};

inline int DTypeSize(DType t) {
  switch (t) {
    case DType::kFloat32: case DType::kInt32: return 4;
    case DType::kFloat64: case DType::kInt64: return 8;
    case DType::kFloat16: case DType::kBfloat16: return 2;
    default: return 1;
  }
}

// Dense C-order host tensor — the cpp-package NDArray analog for the
// deployment surface (device residency is the Predictor's concern).
struct Tensor {
  DType dtype = DType::kFloat32;
  std::vector<int64_t> shape;
  std::vector<uint8_t> data;

  int64_t NumElements() const {
    int64_t n = 1;
    for (int64_t d : shape) n *= d;
    return n;
  }
  int64_t NumBytes() const { return NumElements() * DTypeSize(dtype); }

  template <typename T>
  T* Data() { return reinterpret_cast<T*>(data.data()); }
  template <typename T>
  const T* Data() const {
    return reinterpret_cast<const T*>(data.data());
  }

  static Tensor Make(DType dt, std::vector<int64_t> shp) {
    Tensor t;
    t.dtype = dt;
    t.shape = std::move(shp);
    t.data.resize(static_cast<size_t>(t.NumBytes()));
    return t;
  }
};

// ---------------------------------------------------------------------------
// Checkpoint: .params / .npz read + write (MXNDArrayLoad/Save analog)
// ---------------------------------------------------------------------------
class Checkpoint {
 public:
  static std::map<std::string, Tensor> Load(const std::string& path) {
    void* h = mxio_params_open(path.c_str());
    if (!h) throw std::runtime_error("Checkpoint::Load: cannot open " +
                                     path);
    std::map<std::string, Tensor> out;
    const int n = mxio_params_count(h);
    for (int i = 0; i < n; ++i) {
      int dt = -1;
      int64_t shape[32], nbytes = 0;
      int ndim = mxio_params_info(h, i, &dt, shape, 32, &nbytes);
      if (ndim < 0 || ndim > 32 || dt < 0) {
        // copy the diagnostics BEFORE closing (close frees the handle)
        std::string name = mxio_params_name(h, i);
        std::string descr = mxio_params_descr(h, i);
        mxio_params_close(h);
        throw std::runtime_error(
            "Checkpoint::Load: unsupported entry " + name +
            " (ndim=" + std::to_string(ndim) + ", descr=" + descr + ")");
      }
      Tensor t;
      t.dtype = static_cast<DType>(dt);
      t.shape.assign(shape, shape + ndim);
      t.data.resize(static_cast<size_t>(nbytes));
      if (mxio_params_read(h, i, t.data.data(), nbytes) != nbytes) {
        mxio_params_close(h);
        throw std::runtime_error("Checkpoint::Load: short read");
      }
      out.emplace(mxio_params_name(h, i), std::move(t));
    }
    mxio_params_close(h);
    return out;
  }

  static void Save(const std::string& path,
                   const std::map<std::string, Tensor>& tensors) {
    void* w = mxio_params_writer_open(path.c_str());
    if (!w) throw std::runtime_error("Checkpoint::Save: cannot open " +
                                     path);
    bool ok = true;
    for (const auto& kv : tensors) {
      const Tensor& t = kv.second;
      if (mxio_params_writer_add(
              w, kv.first.c_str(), static_cast<int>(t.dtype),
              static_cast<int>(t.shape.size()), t.shape.data(),
              t.data.data()) != 0) {
        ok = false;
        break;
      }
    }
    if (mxio_params_writer_close(w) != 0 || !ok)
      throw std::runtime_error("Checkpoint::Save: write failed");
  }
};

// ---------------------------------------------------------------------------
// RecordIO (dmlc framing; interchangeable with the Python readers)
// ---------------------------------------------------------------------------
class RecordWriter {
 public:
  explicit RecordWriter(const std::string& path)
      : h_(mxio_recwriter_open(path.c_str())) {
    if (!h_) throw std::runtime_error("RecordWriter: cannot open " + path);
  }
  ~RecordWriter() {
    // destructor must not throw; call Close() explicitly to detect
    // flush failures
    if (h_) {
      mxio_recwriter_close(h_);
      h_ = nullptr;
    }
  }
  void Write(const void* data, size_t len) {
    if (mxio_recwriter_write(h_, static_cast<const uint8_t*>(data),
                             len) != 0)
      throw std::runtime_error("RecordWriter: write failed");
  }
  void Write(const std::string& s) { Write(s.data(), s.size()); }
  void Close() {
    if (h_) {
      int rc = mxio_recwriter_close(h_);
      h_ = nullptr;
      if (rc != 0)
        throw std::runtime_error(
            "RecordWriter: close/flush failed (data may be truncated)");
    }
  }

 private:
  void* h_;
};

class RecordReader {
 public:
  explicit RecordReader(const std::string& path, int prefetch = 64)
      : h_(mxio_reader_open(path.c_str(), prefetch)) {
    if (!h_) throw std::runtime_error("RecordReader: cannot open " + path);
  }
  ~RecordReader() {
    if (h_) mxio_reader_close(h_);
  }
  // false at EOF; throws on a corrupt stream
  bool Next(std::string* out) {
    const uint8_t* data = nullptr;
    size_t len = 0;
    int rc = mxio_reader_next(h_, &data, &len);
    if (rc < 0) throw std::runtime_error("RecordReader: corrupt stream");
    if (rc == 0) return false;
    out->assign(reinterpret_cast<const char*>(data), len);
    return true;
  }
  void Reset() { mxio_reader_reset(h_); }

 private:
  void* h_;
};

}  // namespace cpp
}  // namespace mxtpu

// ---------------------------------------------------------------------------
// Predictor — PJRT-backed TPU inference for exported graphs. Only
// compiled when the PJRT C API header is available (define
// MXTPU_CPP_WITH_PJRT and add the include path; examples/cpp does).
// ---------------------------------------------------------------------------
#ifdef MXTPU_CPP_WITH_PJRT

#include <dlfcn.h>

#include <cstdio>
#include <fstream>
#include <sstream>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace mxtpu {
namespace cpp {

class Predictor {
 public:
  // `prefix`: mx.onnx.export_for_pjrt_c output prefix (.stablehlo,
  // .copts, .manifest). `params_path`: checkpoint with the weights
  // (defaults to prefix + ".params").
  explicit Predictor(const std::string& prefix,
                     std::string params_path = "")
      : prefix_(prefix) {
    if (params_path.empty()) params_path = prefix + ".params";
    params_ = Checkpoint::Load(params_path);
    ParseManifest(ReadFile(prefix + ".manifest"));
    InitClient();
    try {
      Compile();
      // weights go device-resident once here; Forward only moves the
      // data inputs (the MXPredCreate residency contract — repeated
      // Forward calls must not pay full-checkpoint H2D latency)
      UploadParams();
    } catch (...) {
      // a throwing constructor never runs the destructor — release the
      // client/executable/buffers here or every failed construction
      // leaks device memory
      Release();
      throw;
    }
    params_.clear();  // device copies are authoritative now
  }

  struct IOSpec {
    bool is_param;
    std::string key;
    DType dtype;
    std::vector<int64_t> dims;
  };
  const std::vector<IOSpec>& inputs() const { return inputs_; }
  const std::vector<IOSpec>& outputs() const { return outputs_; }

  ~Predictor() { Release(); }
  Predictor(const Predictor&) = delete;
  Predictor& operator=(const Predictor&) = delete;

  // `data_inputs[j]` feeds manifest record `input data j`.
  std::vector<Tensor> Forward(const std::vector<Tensor>& data_inputs) {
    std::vector<PJRT_Buffer*> bufs;
    std::vector<PJRT_Buffer*> out_bufs_guard;
    // any exception below must release already-created device buffers
    // or repeated failing calls leak HBM
    try {
      return ForwardImpl(data_inputs, &bufs, &out_bufs_guard);
    } catch (...) {
      for (auto* b : bufs)
        if (b) DestroyBuffer(b);
      for (auto* b : out_bufs_guard)
        if (b) DestroyBuffer(b);
      throw;
    }
  }

 private:
  // Free every PJRT resource this object owns (destructor body; also
  // the constructor's failure path, where the destructor won't run).
  void Release() {
    for (auto*& b : param_bufs_) {
      if (b) DestroyBuffer(b);
      b = nullptr;
    }
    if (exec_) {
      PJRT_LoadedExecutable_Destroy_Args ld;
      std::memset(&ld, 0, sizeof ld);
      ld.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
      ld.executable = exec_;
      api_->PJRT_LoadedExecutable_Destroy(&ld);
      exec_ = nullptr;
    }
    if (client_) {
      PJRT_Client_Destroy_Args cd;
      std::memset(&cd, 0, sizeof cd);
      cd.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
      cd.client = client_;
      api_->PJRT_Client_Destroy(&cd);
      client_ = nullptr;
    }
  }

  // One H2D transfer. Returns the device buffer; *done receives the
  // done_with_host_buffer event so callers can batch the awaits.
  PJRT_Buffer* TransferToDevice(const Tensor& host, const IOSpec& in,
                                PJRT_Event** done) {
    int64_t want = DTypeSize(in.dtype);
    for (int64_t d : in.dims) want *= d;
    if (host.NumBytes() != want)
      throw std::runtime_error(in.key + ": byte-size mismatch");
    PJRT_Client_BufferFromHostBuffer_Args bh;
    std::memset(&bh, 0, sizeof bh);
    bh.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    bh.client = client_;
    bh.data = host.data.data();
    bh.type = ToPjrtType(in.dtype);
    bh.dims = in.dims.data();
    bh.num_dims = in.dims.size();
    bh.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    bh.device = device_;
    Check(api_->PJRT_Client_BufferFromHostBuffer(&bh), "h2d");
    *done = bh.done_with_host_buffer;
    return bh.buffer;
  }

  // Upload every param input once; all transfers are issued before any
  // await so the copies overlap instead of serializing per-buffer.
  void UploadParams() {
    param_bufs_.assign(inputs_.size(), nullptr);
    std::vector<PJRT_Event*> dones;
    try {
      for (size_t i = 0; i < inputs_.size(); ++i) {
        if (!inputs_[i].is_param) continue;
        auto it = params_.find(inputs_[i].key);
        if (it == params_.end())
          throw std::runtime_error("missing param " + inputs_[i].key);
        PJRT_Event* done = nullptr;
        param_bufs_[i] = TransferToDevice(it->second, inputs_[i], &done);
        dones.push_back(done);
      }
      AwaitAll(&dones, "param h2d done");
    } catch (...) {
      DestroyEvents(&dones);
      for (auto*& b : param_bufs_)
        if (b) { DestroyBuffer(b); b = nullptr; }
      throw;
    }
  }

  std::vector<Tensor> ForwardImpl(const std::vector<Tensor>& data_inputs,
                                  std::vector<PJRT_Buffer*>* bufs_out,
                                  std::vector<PJRT_Buffer*>* outs_guard) {
    // bufs tracks only per-call (data) buffers — params stay resident
    std::vector<PJRT_Buffer*>& bufs = *bufs_out;
    std::vector<PJRT_Buffer*> args(inputs_.size(), nullptr);
    std::vector<PJRT_Event*> dones;
    try {
      for (size_t i = 0; i < inputs_.size(); ++i) {
        const IOSpec& in = inputs_[i];
        if (in.is_param) {
          args[i] = param_bufs_[i];
          continue;
        }
        size_t j = std::stoul(in.key);
        if (j >= data_inputs.size())
          throw std::runtime_error("missing data input " + in.key);
        PJRT_Event* done = nullptr;
        args[i] = TransferToDevice(data_inputs[j], in, &done);
        bufs.push_back(args[i]);
        dones.push_back(done);
      }
      AwaitAll(&dones, "h2d done");
    } catch (...) {
      // buffers are released by Forward's guard; pending events are
      // this scope's to free
      DestroyEvents(&dones);
      throw;
    }

    PJRT_ExecuteOptions eo;
    std::memset(&eo, 0, sizeof eo);
    eo.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
    PJRT_Buffer** arg_list = args.data();
    std::vector<PJRT_Buffer*>& out_bufs = *outs_guard;
    out_bufs.assign(outputs_.size(), nullptr);
    PJRT_Buffer** out_list = out_bufs.data();
    PJRT_LoadedExecutable_Execute_Args ex;
    std::memset(&ex, 0, sizeof ex);
    ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    ex.executable = exec_;
    ex.options = &eo;
    ex.num_devices = 1;
    ex.num_args = args.size();
    ex.argument_lists = &arg_list;
    ex.output_lists = &out_list;
    Check(api_->PJRT_LoadedExecutable_Execute(&ex), "execute");

    std::vector<Tensor> outs;
    for (size_t i = 0; i < outputs_.size(); ++i) {
      Tensor t = Tensor::Make(outputs_[i].dtype, outputs_[i].dims);
      PJRT_Buffer_ToHostBuffer_Args th;
      std::memset(&th, 0, sizeof th);
      th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
      th.src = out_bufs[i];
      th.dst = t.data.data();
      th.dst_size = t.data.size();
      Check(api_->PJRT_Buffer_ToHostBuffer(&th), "d2h");
      Await(th.event, "d2h done");
      outs.push_back(std::move(t));
      DestroyBuffer(out_bufs[i]);
      out_bufs[i] = nullptr;
    }
    for (auto*& b : bufs) {
      DestroyBuffer(b);
      b = nullptr;
    }
    return outs;
  }

  static PJRT_Buffer_Type ToPjrtType(DType t) {
    switch (t) {
      case DType::kFloat32: return PJRT_Buffer_Type_F32;
      case DType::kFloat64: return PJRT_Buffer_Type_F64;
      case DType::kFloat16: return PJRT_Buffer_Type_F16;
      case DType::kUint8: return PJRT_Buffer_Type_U8;
      case DType::kInt32: return PJRT_Buffer_Type_S32;
      case DType::kInt8: return PJRT_Buffer_Type_S8;
      case DType::kInt64: return PJRT_Buffer_Type_S64;
      case DType::kBfloat16: return PJRT_Buffer_Type_BF16;
    }
    return PJRT_Buffer_Type_INVALID;
  }

  static std::string ReadFile(const std::string& path) {
    std::ifstream f(path, std::ios::binary);
    if (!f) throw std::runtime_error("cannot read " + path);
    std::ostringstream ss;
    ss << f.rdbuf();
    return ss.str();
  }

  void Check(PJRT_Error* err, const char* what) {
    if (!err) return;
    PJRT_Error_Message_Args em;
    std::memset(&em, 0, sizeof em);
    em.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
    em.error = err;
    api_->PJRT_Error_Message(&em);
    std::string msg(em.message, em.message_size);
    PJRT_Error_Destroy_Args ed;
    std::memset(&ed, 0, sizeof ed);
    ed.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
    ed.error = err;
    api_->PJRT_Error_Destroy(&ed);
    throw std::runtime_error(std::string(what) + ": " + msg);
  }

  void Await(PJRT_Event* ev, const char* what) {
    PJRT_Event_Await_Args aw;
    std::memset(&aw, 0, sizeof aw);
    aw.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
    aw.event = ev;
    PJRT_Error* err = api_->PJRT_Event_Await(&aw);
    DestroyEvent(ev);
    Check(err, what);
  }

  void DestroyEvent(PJRT_Event* ev) {
    PJRT_Event_Destroy_Args ed;
    std::memset(&ed, 0, sizeof ed);
    ed.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
    ed.event = ev;
    api_->PJRT_Event_Destroy(&ed);
  }

  // Await a batch of transfer events; on ANY failure (including an
  // exception thrown before this is reached, via the caller's catch)
  // un-awaited events must still be destroyed or each failing call
  // leaks one — entries are nulled as Await consumes them.
  void AwaitAll(std::vector<PJRT_Event*>* dones, const char* what) {
    for (auto*& ev : *dones) {
      PJRT_Event* e = ev;
      ev = nullptr;                  // Await destroys it, success or not
      Await(e, what);
    }
  }

  void DestroyEvents(std::vector<PJRT_Event*>* dones) {
    for (auto*& ev : *dones) {
      if (ev) DestroyEvent(ev);
      ev = nullptr;
    }
  }

  void DestroyBuffer(PJRT_Buffer* b) {
    PJRT_Buffer_Destroy_Args bd;
    std::memset(&bd, 0, sizeof bd);
    bd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    bd.buffer = b;
    api_->PJRT_Buffer_Destroy(&bd);
  }

  void ParseManifest(const std::string& mf) {
    if (mf.rfind("mxtpu-pjrt v1", 0) != 0)
      throw std::runtime_error("bad manifest for " + prefix_);
    const char* p = mf.c_str();
    char sub[16], key[512];
    while ((p = std::strchr(p, '\n'))) {
      ++p;
      int dtype, ndim, off = 0;
      IOSpec io;
      if (std::sscanf(p, "input %15s %511s %d %d%n", sub, key, &dtype,
                      &ndim, &off) == 4) {
        io.is_param = std::strcmp(sub, "param") == 0;
      } else if (std::sscanf(p, "output %511s %d %d%n", key, &dtype,
                             &ndim, &off) == 3) {
        io.is_param = false;
        sub[0] = 'o';
        sub[1] = 0;
      } else {
        continue;
      }
      io.key = key;
      io.dtype = static_cast<DType>(dtype);
      const char* q = p + off;
      for (int d = 0; d < ndim; ++d) {
        long long v;
        int o2 = 0;
        if (std::sscanf(q, " %lld%n", &v, &o2) != 1)
          throw std::runtime_error("bad manifest dims");
        io.dims.push_back(v);
        q += o2;
      }
      (sub[0] == 'o' ? outputs_ : inputs_).push_back(std::move(io));
    }
  }

  void InitClient() {
    const char* so_path = std::getenv("MXTPU_PJRT_SO");
    void* so = dlopen(so_path ? so_path : "libtpu.so",
                      RTLD_NOW | RTLD_GLOBAL);
    if (!so) throw std::runtime_error(std::string("dlopen PJRT: ") +
                                      dlerror());
    typedef const PJRT_Api* (*GetApiFn)(void);
    GetApiFn get_api =
        reinterpret_cast<GetApiFn>(dlsym(so, "GetPjrtApi"));
    if (!get_api) throw std::runtime_error("GetPjrtApi not exported");
    api_ = get_api();

    // one-time plugin setup: libtpu refuses every other call before it
    PJRT_Plugin_Initialize_Args pi;
    std::memset(&pi, 0, sizeof pi);
    pi.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    Check(api_->PJRT_Plugin_Initialize(&pi), "plugin initialize");

    PJRT_Client_Create_Args cc;
    std::memset(&cc, 0, sizeof cc);
    cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    Check(api_->PJRT_Client_Create(&cc), "client create");
    client_ = cc.client;

    PJRT_Client_AddressableDevices_Args ad;
    std::memset(&ad, 0, sizeof ad);
    ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    ad.client = client_;
    Check(api_->PJRT_Client_AddressableDevices(&ad), "devices");
    if (ad.num_addressable_devices == 0)
      throw std::runtime_error("no addressable devices");
    device_ = ad.addressable_devices[0];
  }

  void Compile() {
    code_ = ReadFile(prefix_ + ".stablehlo");
    copts_ = ReadFile(prefix_ + ".copts");
    PJRT_Program prog;
    std::memset(&prog, 0, sizeof prog);
    prog.struct_size = PJRT_Program_STRUCT_SIZE;
    prog.code = &code_[0];
    prog.code_size = code_.size();
    static const char kFmt[] = "mlir";
    prog.format = kFmt;
    prog.format_size = sizeof(kFmt) - 1;
    PJRT_Client_Compile_Args co;
    std::memset(&co, 0, sizeof co);
    co.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
    co.client = client_;
    co.program = &prog;
    co.compile_options = copts_.data();
    co.compile_options_size = copts_.size();
    Check(api_->PJRT_Client_Compile(&co), "compile");
    exec_ = co.executable;
  }

  std::string prefix_, code_, copts_;
  std::map<std::string, Tensor> params_;
  // device-resident weights, index-aligned with inputs_ (null for the
  // data slots); uploaded once at construction
  std::vector<PJRT_Buffer*> param_bufs_;
  std::vector<IOSpec> inputs_, outputs_;
  const PJRT_Api* api_ = nullptr;
  PJRT_Client* client_ = nullptr;
  PJRT_Device* device_ = nullptr;
  PJRT_LoadedExecutable* exec_ = nullptr;
};

}  // namespace cpp
}  // namespace mxtpu

#endif  // MXTPU_CPP_WITH_PJRT
