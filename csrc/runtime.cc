// paddle_tpu native runtime: TCPStore rendezvous and a bounded MPMC queue (DataLoader prefetch backbone).
//
// Capability parity (TPU-native re-implementations, not ports):
//  - TCPStore / MasterDaemon:  paddle/fluid/distributed/store/tcp_store.cc
//    (master listens, ranks set/get/add/wait over a tiny length-prefixed
//    protocol on loopback/DCN; bootstrap KV for multi-host rendezvous).
//  - Bounded blocking queue:   the native prefetch core of the reference's
//    DataLoader (paddle/fluid/operators/reader/buffered_reader.cc-class
//    machinery) — Python workers enqueue opaque handles; consumers block in
//    C (GIL released) instead of spinning a Python queue.
//
// Exposed as a plain C ABI for ctypes (pybind11 is not available in this
// image — see repo build notes).

#include <arpa/inet.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace {

using Clock = std::chrono::steady_clock;

// ---------------------------------------------------------------------------
// Length-prefixed framing helpers
// ---------------------------------------------------------------------------

bool send_all(int fd, const void* buf, size_t n) {
  const char* p = static_cast<const char*>(buf);
  while (n > 0) {
    ssize_t w = ::send(fd, p, n, MSG_NOSIGNAL);
    if (w <= 0) return false;
    p += w;
    n -= static_cast<size_t>(w);
  }
  return true;
}

bool recv_all(int fd, void* buf, size_t n) {
  char* p = static_cast<char*>(buf);
  while (n > 0) {
    ssize_t r = ::recv(fd, p, n, 0);
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

bool send_frame(int fd, uint8_t op, const std::string& key,
                const std::string& val) {
  uint32_t kl = htonl(static_cast<uint32_t>(key.size()));
  uint32_t vl = htonl(static_cast<uint32_t>(val.size()));
  return send_all(fd, &op, 1) && send_all(fd, &kl, 4) &&
         send_all(fd, key.data(), key.size()) && send_all(fd, &vl, 4) &&
         send_all(fd, val.data(), val.size());
}

bool recv_frame(int fd, uint8_t* op, std::string* key, std::string* val) {
  uint32_t kl = 0, vl = 0;
  if (!recv_all(fd, op, 1) || !recv_all(fd, &kl, 4)) return false;
  kl = ntohl(kl);
  if (kl > (64u << 10)) return false;
  key->resize(kl);
  if (kl && !recv_all(fd, key->data(), kl)) return false;
  if (!recv_all(fd, &vl, 4)) return false;
  vl = ntohl(vl);
  if (vl > (64u << 20)) return false;
  val->resize(vl);
  if (vl && !recv_all(fd, val->data(), vl)) return false;
  return true;
}

// ops
enum : uint8_t { OP_SET = 1, OP_GET = 2, OP_ADD = 3, OP_WAIT = 4, OP_OK = 5,
                 OP_MISS = 6 };

// ---------------------------------------------------------------------------
// MasterDaemon: the store server
// ---------------------------------------------------------------------------

struct Master {
  int listen_fd = -1;
  int port = 0;
  std::atomic<bool> stop{false};
  std::thread accept_thread;
  std::vector<std::thread> handlers;
  std::mutex fds_mu;
  std::vector<int> client_fds;
  std::mutex mu;
  std::condition_variable cv;
  std::map<std::string, std::string> kv;

  void handle(int fd) {
    uint8_t op;
    std::string key, val;
    while (!stop.load() && recv_frame(fd, &op, &key, &val)) {
      switch (op) {
        case OP_SET: {
          {
            std::lock_guard<std::mutex> l(mu);
            kv[key] = val;
          }
          cv.notify_all();
          if (!send_frame(fd, OP_OK, key, "")) goto done;
          break;
        }
        case OP_GET: {
          std::unique_lock<std::mutex> l(mu);
          auto it = kv.find(key);
          if (it == kv.end()) {
            l.unlock();
            if (!send_frame(fd, OP_MISS, key, "")) goto done;
          } else {
            std::string v = it->second;
            l.unlock();
            if (!send_frame(fd, OP_OK, key, v)) goto done;
          }
          break;
        }
        case OP_ADD: {
          int64_t delta = 0;
          std::memcpy(&delta, val.data(),
                      std::min(val.size(), sizeof(delta)));
          int64_t cur;
          {
            std::lock_guard<std::mutex> l(mu);
            auto it = kv.find(key);
            cur = 0;
            if (it != kv.end() && it->second.size() == 8)
              std::memcpy(&cur, it->second.data(), 8);
            cur += delta;
            std::string v(8, '\0');
            std::memcpy(v.data(), &cur, 8);
            kv[key] = v;
          }
          cv.notify_all();
          std::string v(8, '\0');
          std::memcpy(v.data(), &cur, 8);
          if (!send_frame(fd, OP_OK, key, v)) goto done;
          break;
        }
        case OP_WAIT: {
          // val = 4-byte timeout ms (network order)
          uint32_t tmo = 0;
          if (val.size() == 4) {
            std::memcpy(&tmo, val.data(), 4);
            tmo = ntohl(tmo);
          }
          std::unique_lock<std::mutex> l(mu);
          bool ok = cv.wait_for(l, std::chrono::milliseconds(tmo ? tmo : 1),
                                [&] {
                                  return kv.count(key) > 0 || stop.load();
                                }) && !stop.load();
          l.unlock();
          if (!send_frame(fd, ok ? OP_OK : OP_MISS, key, "")) goto done;
          break;
        }
        default:
          goto done;
      }
    }
  done:
    {
      // deregister before closing: stop() shutdown()s every fd still in
      // client_fds, and the OS may have reassigned a closed fd number to
      // an unrelated descriptor in this process
      std::lock_guard<std::mutex> l(fds_mu);
      client_fds.erase(std::remove(client_fds.begin(), client_fds.end(), fd),
                       client_fds.end());
    }
    ::close(fd);
  }

  void run() {
    while (!stop.load()) {
      int fd = ::accept(listen_fd, nullptr, nullptr);
      if (fd < 0) {
        if (stop.load()) break;
        continue;
      }
      int one = 1;
      setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
      {
        std::lock_guard<std::mutex> l(fds_mu);
        client_fds.push_back(fd);
      }
      handlers.emplace_back([this, fd] { handle(fd); });
    }
  }
};

// ---------------------------------------------------------------------------
// Client
// ---------------------------------------------------------------------------

struct Client {
  int fd = -1;
  std::mutex mu;  // one request in flight per client
};

// ---------------------------------------------------------------------------
// Bounded MPMC queue of opaque pointers
// ---------------------------------------------------------------------------

struct Queue {
  std::mutex mu;
  std::condition_variable not_full, not_empty;
  std::deque<void*> items;
  size_t cap;
  std::atomic<bool> closed{false};
  explicit Queue(size_t c) : cap(c) {}
};

}  // namespace

extern "C" {

// ------------------------------- store -------------------------------------

void* pd_store_master_start(int port) {
  auto* m = new Master();
  m->listen_fd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (m->listen_fd < 0) {
    delete m;
    return nullptr;
  }
  int one = 1;
  setsockopt(m->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_addr.s_addr = htonl(INADDR_ANY);
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (::bind(m->listen_fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) <
          0 ||
      ::listen(m->listen_fd, 128) < 0) {
    ::close(m->listen_fd);
    delete m;
    return nullptr;
  }
  socklen_t len = sizeof(addr);
  getsockname(m->listen_fd, reinterpret_cast<sockaddr*>(&addr), &len);
  m->port = ntohs(addr.sin_port);
  m->accept_thread = std::thread([m] { m->run(); });
  return m;
}

int pd_store_master_port(void* h) { return static_cast<Master*>(h)->port; }

void pd_store_master_stop(void* h) {
  auto* m = static_cast<Master*>(h);
  m->stop.store(true);
  ::shutdown(m->listen_fd, SHUT_RDWR);
  ::close(m->listen_fd);
  if (m->accept_thread.joinable()) m->accept_thread.join();
  {
    // unblock every handler stuck in recv_frame, then join — no thread may
    // outlive the Master it dereferences
    std::lock_guard<std::mutex> l(m->fds_mu);
    for (int fd : m->client_fds) ::shutdown(fd, SHUT_RDWR);
  }
  m->cv.notify_all();
  for (auto& t : m->handlers)
    if (t.joinable()) t.join();
  delete m;
}

void* pd_store_client_connect(const char* host, int port, int timeout_ms) {
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) return nullptr;
  auto deadline = Clock::now() + std::chrono::milliseconds(timeout_ms);
  int fd = -1;
  for (;;) {
    // fresh socket per attempt: after a failed connect the fd is left in
    // an error state and every further connect on it fails immediately,
    // which used to turn the retry window into a single shot — a client
    // racing the master's bind could then never get in at all
    fd = ::socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return nullptr;
    if (::connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) == 0)
      break;
    ::close(fd);
    if (Clock::now() > deadline) return nullptr;
    std::this_thread::sleep_for(std::chrono::milliseconds(50));
  }
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  auto* c = new Client();
  c->fd = fd;
  return c;
}

void pd_store_client_close(void* h) {
  auto* c = static_cast<Client*>(h);
  ::close(c->fd);
  delete c;
}

int pd_store_set(void* h, const char* key, const uint8_t* data, int len) {
  auto* c = static_cast<Client*>(h);
  std::lock_guard<std::mutex> l(c->mu);
  if (!send_frame(c->fd, OP_SET, key,
                  std::string(reinterpret_cast<const char*>(data), len)))
    return -1;
  uint8_t op;
  std::string k, v;
  return recv_frame(c->fd, &op, &k, &v) && op == OP_OK ? 0 : -1;
}

// returns value length, or -1 on miss/error; copies min(cap, len) bytes
int pd_store_get(void* h, const char* key, uint8_t* out, int cap) {
  auto* c = static_cast<Client*>(h);
  std::lock_guard<std::mutex> l(c->mu);
  if (!send_frame(c->fd, OP_GET, key, "")) return -1;
  uint8_t op;
  std::string k, v;
  if (!recv_frame(c->fd, &op, &k, &v) || op != OP_OK) return -1;
  int n = static_cast<int>(v.size());
  std::memcpy(out, v.data(), std::min(n, cap));
  return n;
}

int pd_store_add(void* h, const char* key, long long delta, long long* out) {
  auto* c = static_cast<Client*>(h);
  std::lock_guard<std::mutex> l(c->mu);
  std::string payload(8, '\0');
  int64_t d = delta;
  std::memcpy(payload.data(), &d, 8);
  if (!send_frame(c->fd, OP_ADD, key, payload)) return -1;
  uint8_t op;
  std::string k, v;
  if (!recv_frame(c->fd, &op, &k, &v) || op != OP_OK || v.size() != 8)
    return -1;
  int64_t r;
  std::memcpy(&r, v.data(), 8);
  *out = r;
  return 0;
}

int pd_store_wait(void* h, const char* key, int timeout_ms) {
  auto* c = static_cast<Client*>(h);
  std::lock_guard<std::mutex> l(c->mu);
  uint32_t tmo = htonl(static_cast<uint32_t>(timeout_ms));
  std::string payload(4, '\0');
  std::memcpy(payload.data(), &tmo, 4);
  if (!send_frame(c->fd, OP_WAIT, key, payload)) return -1;
  uint8_t op;
  std::string k, v;
  return recv_frame(c->fd, &op, &k, &v) && op == OP_OK ? 0 : -1;
}

// ------------------------------- queue -------------------------------------

void* pd_queue_new(int capacity) { return new Queue(capacity); }

void pd_queue_close(void* h) {
  auto* q = static_cast<Queue*>(h);
  q->closed.store(true);
  q->not_empty.notify_all();
  q->not_full.notify_all();
}

void pd_queue_free(void* h) { delete static_cast<Queue*>(h); }

// item is an opaque non-null pointer (Python passes an integer token).
// returns 0 ok, -1 timeout/closed
int pd_queue_put(void* h, void* item, int timeout_ms) {
  auto* q = static_cast<Queue*>(h);
  std::unique_lock<std::mutex> l(q->mu);
  if (!q->not_full.wait_for(l, std::chrono::milliseconds(timeout_ms), [&] {
        return q->items.size() < q->cap || q->closed.load();
      }))
    return -1;
  if (q->closed.load()) return -1;
  q->items.push_back(item);
  l.unlock();
  q->not_empty.notify_one();
  return 0;
}

// returns item or nullptr on timeout/closed-and-empty
void* pd_queue_get(void* h, int timeout_ms) {
  auto* q = static_cast<Queue*>(h);
  std::unique_lock<std::mutex> l(q->mu);
  if (!q->not_empty.wait_for(l, std::chrono::milliseconds(timeout_ms), [&] {
        return !q->items.empty() || q->closed.load();
      }))
    return nullptr;
  if (q->items.empty()) return nullptr;
  void* it = q->items.front();
  q->items.pop_front();
  l.unlock();
  q->not_full.notify_one();
  return it;
}

int pd_queue_size(void* h) {
  auto* q = static_cast<Queue*>(h);
  std::lock_guard<std::mutex> l(q->mu);
  return static_cast<int>(q->items.size());
}

}  // extern "C"
