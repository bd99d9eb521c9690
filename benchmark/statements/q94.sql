SELECT COUNT(DISTINCT ws1.ws_order_number) AS order_count,
       SUM(ws1.ws_ext_ship_cost) AS total_shipping_cost,
       SUM(ws1.ws_net_profit) AS total_net_profit
FROM web_sales ws1, date_dim, customer_address, web_site
WHERE d_date BETWEEN '{date_lo}' AND '{date_hi}'
  AND ws1.ws_ship_date_sk = d_date_sk
  AND ws1.ws_ship_addr_sk = ca_address_sk AND ca_state = '{state}'
  AND ws1.ws_web_site_sk = web_site_sk AND web_company_name = '{company}'
  AND EXISTS (SELECT * FROM web_sales ws2
              WHERE ws1.ws_order_number = ws2.ws_order_number
                AND ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)
  AND NOT EXISTS (SELECT * FROM web_returns wr1
                  WHERE ws1.ws_order_number = wr1.wr_order_number)
ORDER BY order_count
LIMIT 100
