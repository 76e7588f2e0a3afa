/* paddle_tpu plain-C ABI — declarations for the native runtime exported by
 * csrc/runtime.cc (built as libpaddle_tpu_runtime.so; loaded via ctypes from
 * paddle_tpu/core/native.py). External C++ extensions compile against this
 * header; paths come from paddle.sysconfig.get_include()/get_lib().
 *
 * Parity role: the reference ships its C++ surface via pybind11 headers;
 * this build's binding strategy is a stable C ABI instead (pybind11 absent
 * in the image). */
#ifndef PADDLE_TPU_C_API_H_
#define PADDLE_TPU_C_API_H_

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

/* ---- TCPStore rendezvous (reference: paddle/fluid/distributed/store) ---- */
void* pd_store_master_start(int port);       /* 0 picks a free port */
int   pd_store_master_port(void* master);
void  pd_store_master_stop(void* master);

void* pd_store_client_connect(const char* host, int port, int timeout_ms);
void  pd_store_client_close(void* client);
int   pd_store_set(void* client, const char* key, const uint8_t* data,
                   int len);
/* returns value length (may exceed cap: retry with a bigger buffer) */
int   pd_store_get(void* client, const char* key, uint8_t* out, int cap);
int   pd_store_add(void* client, const char* key, long long delta,
                   long long* out);
int   pd_store_wait(void* client, const char* key, int timeout_ms);

/* ---- MPMC prefetch queue (reference: paddle/fluid/operators/reader) ---- */
void* pd_queue_new(int capacity);
/* item ownership transfers to the queue; 0 on success, -1 on timeout/closed */
int   pd_queue_put(void* q, void* item, int timeout_ms);
void* pd_queue_get(void* q, int timeout_ms);  /* NULL on timeout/closed */
int   pd_queue_size(void* q);
void  pd_queue_close(void* q);
void  pd_queue_free(void* q);

#ifdef __cplusplus
}  /* extern "C" */
#endif

#endif  /* PADDLE_TPU_C_API_H_ */
